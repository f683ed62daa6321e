"""One optimizer step of the compiled epoch program with its input staged,
over the whole window: the sum of ``train()``'s own ``epoch_time`` (its
clock around batch staging and the scanned program, stopped on a readback)
over all the steps of the window's epochs.  A stalled epoch moves it."""


def read(run):
    return 1e3 * sum(h["epoch_time"] for h in run["epochs"]) / (
        len(run["epochs"]) * run["steps"])
