"""Device: peak device memory on the fullest chip in MiB: the larger of the
allocator's two peaks, `peak_bytes_in_use` (live arrays) and
`peak_bytes_reserved` (the scratch space of XLA programs); see
`observe.device_facts` for why not their sum."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes", 0)
    return peak / 2**20 if peak else None
