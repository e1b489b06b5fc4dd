"""How uneven the routing was over all the router's experts, held here
or not: the rows of the fullest expert over the mean expert's rows,
summed over the window's steps and layers (1.0: perfectly even). This
is the quantity an expert bias exists to hold near 1, and the one its
update moves from step to step. Source: the program's counters
(`moe.route_rows_max`, `moe.route_rows`, from the loss's auxiliary
output where the layers count their routes), kept by the driver; a
program whose layers do not count them gives no reading."""


def read(ctx):
    c = ctx["counters"]
    experts = ctx["config"].get("router_width")
    if not experts or not c.get("moe.route_rows"):
        return None
    return c["moe.route_rows_max"] / (c["moe.route_rows"] / experts)
