"""Bits the workers put on the wire over the bits of all-raw
point-to-point transmission of the same rounds, from the program's
CommLedger, over the workload's first ``wire_rounds`` rounds."""


def read(ctx):
    return ctx["counters"].get("wire_bits_ratio")
