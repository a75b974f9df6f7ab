"""The census GEMMs' roofline time (per launch the larger of FLOPs over
the bf16 peak and least bytes over the memory bandwidth, times its
count) over their device time in the profile of the timing rounds, in
percent.  The launches in the profile must be the census's, shape for
shape, in every round."""

import collections

from benchmarks.chip import trace as tr
from benchmarks.chip.harness import log
from benchmarks.chip.readers import census_roofline_s


def read(run):
    trace, launches = run.get("census_trace"), (run.get("census") or {}).get("tune")
    if trace is None or not launches or not run.get("rounds") or run.get("peak") is None:
        return None
    events = tr.kernel_events(trace, "gemm")
    found = collections.Counter(tr.launch_dims("gemm", e) for e in events)
    want = collections.Counter({c.dims: c.count * run["rounds"] for c in launches})
    if found != want:
        log(f"gemm in the timing rounds: {dict(found)} in the trace, census says {dict(want)}")
        return None
    device_s = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * census_roofline_s(launches, run["peak"]) * run["rounds"] / device_s
