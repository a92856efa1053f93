"""End-to-end: process start to the end of set-up (imports, weights on the
device, the first steps compiled or from the cache, the cost model's
drain): what a restart costs a user."""


def read(obs):
    return obs["setup"]["seconds"]
