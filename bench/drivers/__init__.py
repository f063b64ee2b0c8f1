"""Drivers: `run(run) -> Outcome` for one kind of system under test; a
configuration names its driver under "driver"."""
