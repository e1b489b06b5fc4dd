"""The whole step's share of the chip's peak, in percent: model FLOPs
of a step (from shapes, `flops/`; nothing recomputed counts) times the
steps completed in the window, over the window's span and the bf16 peak
of the chips used. Source: the host clock around the window."""


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if not peaks or not w["steps"]:
        return None
    done = ctx["flops_per_step"] * w["steps"]
    return 100.0 * done / (w["span_s"] * peaks["bf16_flops_per_s"]
                           * ctx["chips"])
