"""The census GEMMs' FLOPs over the host-clock time of a timing round
(``tuned_gemm_ms``: dispatch, launches and kernels), as a percent of the
bf16 peak: the whole prefill GEMM set's share, which bounds what
``gemm_roofline.tune`` can show end to end."""

from benchmarks.chip.readers import census_flops


def read(run):
    launches = (run.get("census") or {}).get("tune")
    if not launches or not run.get("tuned_s") or run.get("peak") is None:
        return None
    return 100.0 * census_flops(launches) / run["tuned_s"] / run["peak"].bf16_flops
