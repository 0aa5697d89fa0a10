"""Device busy time per FL round: busy seconds of the traced window
(averaged over devices) over the rounds it completed, in ms."""


def read(ctx):
    rounds = ctx["run"].get("rounds")
    if not rounds:
        return None
    return 1000.0 * ctx["busy_s"] / rounds
