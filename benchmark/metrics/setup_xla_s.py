"""Layer 'compile reuse': seconds set-up spent in backend compiles less the
cache loads inside them, XLA's compile on a persistent-cache miss and the
key and lookup on a hit (the program's ``tpudl_compile_xla_seconds``,
summed in the registry snapshot taken as the window starts: all of
set-up's).  ``None`` where the program keeps no such series."""


def read(obs):
    held = obs["counters"]["before"].get("tpudl_compile_xla_seconds")
    return None if held is None else held[0]
