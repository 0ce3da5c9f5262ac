"""The share of the traced stretch in which no operation ran on the
device, in %; with several chips, of the busiest one."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["n_devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s_max"] / tr["window_s"])
