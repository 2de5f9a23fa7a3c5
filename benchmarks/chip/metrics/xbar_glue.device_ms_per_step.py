"""Digital work around the crossbar kernels: device ms a step of every
operation in the program's ``xbar.read`` and ``xbar.write`` scopes that
is not a kernel (DAC full-scale reductions, drive and write-driver
quantisation, padding to whole tiles, slicing, the rail fraction)."""
import scopes


def read(run):
    return scopes.digital_ms_per_step(run, "xbar.read", "xbar.write")
