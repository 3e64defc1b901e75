"""Seconds the process spent tracing, lowering and compiling jitted programs
(or loading them from the persistent cache) before the reading, all in the
set-up: the program's compile counter, ``repro.obs.compile_stats()``. None
where the program has no counter."""


def read(run):
    try:
        from repro.obs import compile_stats
    except ImportError:
        return None
    return compile_stats()["seconds"]
