"""Embedding lookup, final norm, tied head and cross-entropy (scope
``head_loss``), with their backward: device ms a step."""
import scopes


def read(run):
    return scopes.digital_ms_per_step(run, "head_loss")
