"""Compiles inside the serving window: fresh executables the engine
built (``cache_report()["compiles"]``) plus XLA backend compiles that
JAX reported.  A warm window has none."""


def read(run):
    n = run.get("compiles_window")
    return None if n is None else float(n)
