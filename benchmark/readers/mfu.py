"""Model FLOP/s utilisation, in %: the operations the forward and backward
passes require per unit of work (recomputation not counted) times the
window's rate per chip, over the device's peak.  An end-to-end
utilisation, not a kernel's roofline share."""


def read(ctx: dict, params: dict):
    rate = ctx["rate_per_chip"]
    if rate is None:
        return None
    return 100.0 * ctx["costs"][params["flops_per_unit"]] * rate / ctx["peaks"]["bf16_flops_per_s"]
