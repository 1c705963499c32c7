"""The driver of each kind of traffic mix. A later PR adds a mix of one of
these kinds as a data file; a new KIND is new harness code."""
from . import serve_driver, train_driver

DRIVERS = {"train": train_driver.run, "open_loop": serve_driver.run}
