"""Routing and dispatch of the expert layers outside the kernels (scopes
``moe.route`` and ``moe.dispatch``: router, softmax, top-k, balance
loss, the sort, the gathers into the experts' buffers and the combine):
device ms a step."""
import moe_scopes


def read(run):
    red = moe_scopes.for_run(run)
    if red is None or not any(n in red["digital"]
                              for n in ("moe.route", "moe.dispatch")):
        return None
    return 1e3 * (red["digital"].get("moe.route", 0.0)
                  + red["digital"].get("moe.dispatch", 0.0)) / run["steps"]
