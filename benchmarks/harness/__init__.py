"""The yardstick: clock, loader, seams, references, trace reduction, peaks."""
