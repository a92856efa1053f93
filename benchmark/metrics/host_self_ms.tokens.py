"""Layer 'entry points': the loop's own python per step: the whole
iteration less the jitted call and less the listeners.  With
``dispatch_ms`` the floor under the step time whatever the device does."""

import program_counters


def read(obs):
    return program_counters.per_step_ms(
        obs, "tokens", ("tpudl_train_iteration_seconds",),
        ("tpudl_train_dispatch_seconds", "tpudl_train_read_seconds"))
