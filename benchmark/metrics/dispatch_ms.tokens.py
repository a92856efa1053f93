"""Layer 'jit step': host time to enqueue one step (inside the jitted
call, which returns before the device is done)."""

import program_counters


def read(obs):
    return program_counters.per_step_ms(
        obs, "tokens", ("tpudl_train_dispatch_seconds",))
