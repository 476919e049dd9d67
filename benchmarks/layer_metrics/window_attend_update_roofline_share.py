"""Kernels: the least seconds the window layers' attention needs over one
update — forward and backward over the BAND's pairs alone, q, k, v and the
result moved once a pass (harness/flops_swa.py) — over the device time under
`update_epoch/.../window_attend`: the banded flash kernel pair, whatever
tile it walks, or whatever else implements the attend."""

from benchmarks.harness import program_reads_lm


def read(ctx):
    return program_reads_lm.roofline_share(
        ctx, ["update_epoch", "window_attend"], "window_attend_update_cost"
    )
