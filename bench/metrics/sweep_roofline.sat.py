"""The relaxation-sweep kernel's share of its HBM roofline, in %, in
the read-saturated cell (`benchlib.devicetrace.sweep_roofline`)."""
from benchlib import devicetrace


def read(run):
    return devicetrace.sweep_roofline(run)
