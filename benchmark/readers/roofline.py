"""A roofline share: the least time the chip could take for the
placements of the measured window (``roofline.py``, from the harness's
own job records) over the device time the named programs took for them.

Metric file: ``{"reader": "roofline", "programs": [regex, ...],
"scale": 100}``. The placements are those of every job done in the
window. The device is traced for a few of the window's seconds only, so
the programs' device time over the whole window is their share of the
traced seconds (launches cut by the trace's ends counted for the part
inside) times the window's length. Nothing where no such program ran in
the traced seconds.
"""

import re
import sys

from .. import roofline


def read(metric: dict, ctx: dict):
    share = sum(sec for name, sec in ctx["trace"]["program_s"].items()
                if any(re.search(p, name) for p in metric["programs"])
                ) / ctx["trace"]["window_s"]
    if share <= 0 or ctx["rehearsal"]:
        return None
    jobs = [r.plain for r in ctx["records"]
            if r.t_done is not None and ctx["t0"] < r.t_done <= ctx["t1"]]
    if not jobs:
        return None
    device_s = share * (ctx["t1"] - ctx["t0"])
    peak = roofline.peak_of(ctx["peaks"], ctx["device"].device_kind)
    least = roofline.least_seconds(
        jobs, ctx["config"]["cluster"]["nodes"], peak)
    print(f"roofline: {least['steps']} steps of {len(jobs)} jobs, "
          f"{least['bytes']} bytes, {least['operations']} operations, "
          f"least {least['seconds']:.6f} s ({least['bound']}-bound) over "
          f"{device_s:.3f} s of device time ({share * 100:.1f}% of the "
          f"window)", file=sys.stderr, flush=True)
    return least["seconds"] / device_s * metric.get("scale", 1.0)
