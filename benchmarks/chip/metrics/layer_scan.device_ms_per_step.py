"""The layer scan's own work (scope ``layer_scan``, not ``layer``):
slicing each layer's containers and activations out of the stacked
trees and stacking the results back, forward and backward; device ms a
step."""
import scopes


def read(run):
    return scopes.digital_ms_per_step(run, "layer_scan")
