"""Network block: MiB of the token policy's decode carry on one chip — the
conv layers' tails and the attention layers' keys and values — as the
program publishes it at learner set-up (gauge `stoix_tpu_lm_carry_bytes`,
by kind). A program without the gauge gives None."""


def read(ctx):
    marks = getattr(ctx, "registry_marks", None)
    if not marks:
        return None
    found = [
        value
        for (name, _, field), value in marks[-1][2].items()
        if name == "stoix_tpu_lm_carry_bytes" and field == "value"
    ]
    return sum(found) / 2**20 if found else None
