"""What several per-layer readers share: the matchers for the service's
Pallas kernels in a device trace, a kernel's roofline share, and the
device's idle share."""
from bench import roofline

PALLAS = 'custom_call_target="tpu_custom_call"'


def is_predict_kernel(name: str) -> bool:
    """`bayes_predict`: the custom call inside the jitted predictive,
    which names the instruction after it."""
    return name.startswith("%_bayes_predict_jit") and PALLAS in name


def is_fit_kernel(name: str) -> bool:
    """`bayes_fit`: three (T, N) float32 inputs, and the four posterior
    outputs (T,2), (T,4), (T,2), (T,5)."""
    if PALLAS not in name:
        return False
    head = name.split(" custom-call(", 1)[0]
    return all(f",{k}]" in head for k in (2, 4, 5))


def kernel_roofline(ctx, match, cost):
    """Share (%) of the chip's roofline that the kernels `match` accepts
    reached over the window, for the useful work `cost` gives as (flops,
    bytes); None where the trace holds no such kernel."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    secs, n = tr.kernel_seconds(match)
    if not n or secs <= 0:
        return None
    flops, bytes_ = cost
    return roofline.share(flops, bytes_, secs, ctx["device_kind"])[0]


def device_idle(ctx):
    """Share (%) of the traced window in which no operation ran on the
    device: 1 - (union of device-op intervals) / window."""
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
