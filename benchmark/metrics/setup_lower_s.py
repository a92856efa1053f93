"""Layer 'compile reuse': seconds set-up spent lowering jaxprs to MLIR
modules (the program's ``tpudl_compile_lower_seconds``, summed in the
registry snapshot taken as the window starts: all of set-up's).  ``None``
where the program keeps no such series."""


def read(obs):
    held = obs["counters"]["before"].get("tpudl_compile_lower_seconds")
    return None if held is None else held[0]
