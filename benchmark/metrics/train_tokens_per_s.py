"""End-to-end: tokens trained per second over the whole window."""

import readers


def read(obs):
    return readers.rate(obs, "tokens")
