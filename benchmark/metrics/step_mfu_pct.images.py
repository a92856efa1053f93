"""Layer 'jit step': the whole step's share of the chip's bf16 peak."""

import readers


def read(obs):
    return readers.step_mfu_pct(obs, "images")
