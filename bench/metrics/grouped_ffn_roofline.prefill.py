"""Roofline share of the Pallas grouped_ffn kernel in the scheduler's
prefill calls, in % (as grouped_ffn_roofline.decode): each call runs the
kernel once per layer over (group x prompt length x top-k) rows; the
occupied experts per layer are the prefill's activated-experts counter."""
from reading import kernel_roofline


def read(run):
    if run.trace is None:
        return None
    k = run.z["k"]

    def jobs(ev):
        g, t = ev[1].shape
        return [(g * t * k, int(a)) for a in ev[4]]
    return kernel_roofline(run, "prefill", jobs)
