"""The benchmark's yardstick: traffic, reference, trace reduction, work counts.

Nothing in this package imports the system under test (`repro`) but the
drivers (`harness/drivers/`), which build and run it; each imports it
only inside the functions that do.
"""
