"""Digital interior of the train step (attention, norms, embedding and
head, loss, SGD of the digital leaves, the padding copies around the
kernels): device time per step of every operation that is not one of
the crossbar kernels, in ms."""


def read(run):
    if not run.get("steps") or run["trace"]["busy_s"] <= 0.0:
        return None
    return 1e3 * run["trace"]["other_s"] / run["steps"]
