"""Learner program: model passes over a block in the rollout, denoise and
commit, a token generated, as the program counts them (TRAIN metric
`decode_passes_per_token`): (S + 1) / B and the prompt's one commit pass.
One-token-a-step decoding reads 1.0; mean over the windows of the interval."""


def read(ctx):
    rate = getattr(ctx, "rate", None)
    if rate is None:
        return None
    passes = [
        record["decode_passes_per_token"]
        for index, record in getattr(ctx, "train", ())
        if rate.first < index <= rate.last and "decode_passes_per_token" in record
    ]
    return sum(passes) / len(passes) if passes else None
