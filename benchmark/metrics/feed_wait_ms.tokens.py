"""Layer 'device feed': the step loop's wait for the feeder, per step."""

import readers


def read(obs):
    return readers.feed_wait_ms(obs, "tokens")
