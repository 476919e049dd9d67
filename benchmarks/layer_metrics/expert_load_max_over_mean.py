"""Network block: the most loaded expert's routed (token, slot) pairs over
the mean expert's in a minibatch of the update, as the program logs it
(TRAIN metric `expert_load_max_over_mean`), mean over the whole windows of
the interval. 1.0 is a perfectly balanced router; the grouped matmuls'
longest group, and in an expert-parallel layout the slowest chip, scale with
it. The reference recomputes it from its own routing (`update_expert_load`)."""


def read(ctx):
    rate = getattr(ctx, "rate", None)
    if rate is None:
        return None
    loads = [
        record["expert_load_max_over_mean"]
        for index, record in getattr(ctx, "train", ())
        if rate.first < index <= rate.last and "expert_load_max_over_mean" in record
    ]
    return sum(loads) / len(loads) if loads else None
