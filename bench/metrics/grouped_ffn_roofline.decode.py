"""Roofline share of the Pallas grouped_ffn kernel in the fused decode
scan, in %: the least time the chip needs for the work (each occupied
expert's weights read once, each row in and out once, or the
operations at peak, whichever is longer) over the kernel's device time,
with that of the ops that copied its expert weights into fast memory
for it, summed over the decode calls in the traced slice. The occupied experts
per layer and step are the program's activated-experts counter."""
from reading import kernel_roofline


def read(run):
    if run.trace is None:
        return None
    k = run.z["k"]

    def jobs(ev):
        ok, act = ev[2][1], ev[2][3]
        return [(int(ok[j].sum()) * k, int(act[j, l]))
                for j in range(ok.shape[0]) for l in range(act.shape[1])]
    return kernel_roofline(run, "fused_decode", jobs)
