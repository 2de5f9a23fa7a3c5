"""Fused read kernel (``kernels/xbar_vmm``) time replayed by the
rematerialised backward of the layer scan: device ms a step of the
forward reads run again under JAX's ``rematted_computation``
(``scopes.py``).  Forward, backward and recompute add up to the read
kernel's time."""
import scopes


def read(run):
    red = scopes.for_run(run)
    if red is None or sum(red["reads"].values()) <= 0.0:
        return None
    return 1e3 * red["reads"]["recompute"] / run["steps"]
