"""End-to-end: images trained per second over the whole window."""

import readers


def read(obs):
    return readers.rate(obs, "images")
