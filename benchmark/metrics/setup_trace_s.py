"""Layer 'compile reuse': seconds set-up spent tracing jitted functions to
jaxprs, the outermost trace on each thread (the program's
``tpudl_compile_trace_seconds``, summed in the registry snapshot taken as
the window starts: all of set-up's).  ``None`` where the program keeps no
such series."""


def read(obs):
    held = obs["counters"]["before"].get("tpudl_compile_trace_seconds")
    return None if held is None else held[0]
