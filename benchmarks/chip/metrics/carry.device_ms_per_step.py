"""Periodic carry (scope ``xbar.carry``): the blend of the primary and
carry arrays before each read and the carry sweep every
``carry_period`` steps; device ms a step, the sweep averaged over the
window's steps."""
import scopes


def read(run):
    return scopes.digital_ms_per_step(run, "xbar.carry")
