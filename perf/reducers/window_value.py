"""A value the traffic worked out over the whole window (the dictionary
its ``run`` returns under ``end_to_end``), read as a per-layer metric."""


def reduce(obs, name):
    return obs["window"].get(name)
