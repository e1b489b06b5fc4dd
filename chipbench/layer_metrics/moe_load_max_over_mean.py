"""How uneven the routing was over the window: the rows of the fullest
held expert over the mean of the held experts' rows, summed over the
window's steps and layers (1.0: perfectly even). Source: the program's
counters (`moe.rows_max_expert`, `moe.rows_held`, from the loss's
auxiliary output), kept by the driver."""


def read(ctx):
    c = ctx["counters"]
    held = ctx["config"].get("num_experts")
    if not held or not c.get("moe.rows_held"):
        return None
    return c["moe.rows_max_expert"] / (c["moe.rows_held"] / held)
