"""Device busy time of the traced window over its steps, in ms.
Source: the device trace."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["busy_s"] / t["steps"]
