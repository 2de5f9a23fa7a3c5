"""Attention outside its projections (``models/layers.attention``, scope
``attention``: head split, rope, the score and value einsums, softmax):
device ms a step."""
import scopes


def read(run):
    return scopes.digital_ms_per_step(run, "attention")
