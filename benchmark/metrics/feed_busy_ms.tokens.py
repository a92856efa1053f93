"""Layer 'device feed': the producer's busy time per batch: ``next()`` on
the iterator plus staging (bucket pad, placement, ``device_put``'s call).
As it nears the step time the feeder sets the pace."""

import program_counters


def read(obs):
    return program_counters.per_step_ms(
        obs, "tokens",
        ("tpudl_data_source_seconds", "tpudl_data_stage_seconds"))
