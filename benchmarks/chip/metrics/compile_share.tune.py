"""Percent of the search window spent compiling candidate programs
(``MeasureStats.compile_s``)."""


def read(run):
    if run.get("compile_s") is None or not run.get("window_s"):
        return None
    return 100.0 * run["compile_s"] / run["window_s"]
