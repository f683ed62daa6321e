"""Worker-samples through forward/backward, update and gossip per second of
wall clock, over the whole epochs of the window, boundary work included."""


def read(run):
    return run["samples"] / run["wall_s"]
