"""Device ms per decode step of the program's ``attention`` scope, less
the cache write (``attention/kv_write``, read apart): the leaf ops of
the fused decode calls in the traced slice whose op_name lies deepest
in it (tracing.charge), over the steps those calls ran."""
import reading


def read(run):
    per = reading.decode_scope_ms(run)
    return None if per is None else per.get("attention")
