"""Layer 'compile reuse': seconds the cost model's analyses took during
set-up, their own compiles included, on their worker thread beside the
first steps (the program's ``tpudl_perf_analysis_seconds``, summed in the
registry snapshot taken as the window starts, after the harness's drain).
``None`` where the program keeps no such series."""


def read(obs):
    held = obs["counters"]["before"].get("tpudl_perf_analysis_seconds")
    return None if held is None else held[0]
