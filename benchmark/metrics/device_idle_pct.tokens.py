"""Layer 'device': idle share of the chip in the traced part of the window."""

import readers


def read(obs):
    return readers.device_idle_pct(obs, "tokens")
