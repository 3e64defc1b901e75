"""Temporaries of the compiled round step, as the compiler reports them."""


def read(run):
    analysis = run["program"].compiled.memory_analysis()
    return analysis.temp_size_in_bytes / 2 ** 30
