"""Device ms per decode step of the whole MoE layer: the program's
``moe`` scope and every scope under it (routing and XShare's selection,
dispatch, the experts, combine), over the fused decode calls in the
traced slice (tracing.charge)."""
import reading


def read(run):
    per = reading.decode_scope_ms(run)
    moe = [v for k, v in (per or {}).items()
           if k == "moe" or k.startswith("moe/")]
    return sum(moe) if moe else None
