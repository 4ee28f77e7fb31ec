"""Device ms per decode step of the program's ``attention/kv_write``
scope, the write of each step's keys and values into the running cache,
over the fused decode calls in the traced slice (tracing.charge)."""
import reading


def read(run):
    per = reading.decode_scope_ms(run)
    return None if per is None else per.get("attention/kv_write")
