"""Device ms per decode step of the program's ``moe/route`` scope: the
router and XShare's selection of the batch's expert set, over the fused
decode calls in the traced slice (tracing.charge)."""
import reading


def read(run):
    per = reading.decode_scope_ms(run)
    return None if per is None else per.get("moe/route")
