"""The benchmark's yardstick: traffic, reference, trace reduction, work counts.

Nothing in this package imports the system under test (`repro`); the
client (`harness.serve`) is the one module that does, to run it.
"""
