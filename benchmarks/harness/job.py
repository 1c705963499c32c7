"""What one run is given and what it hands back, whatever the kind of mix."""
import dataclasses
import importlib.util
import os
import shutil
import sys
import time

START = time.perf_counter()     # run.py moves it back to its own first line


def log(msg):
    """A line on standard error, stamped with the seconds since the process
    started: where set-up goes is read off these."""
    print(f"[bench {time.perf_counter() - START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


@dataclasses.dataclass
class Job:
    root: str               # the checkout
    bench: dict             # BENCHMARK.json
    workload: dict          # its entry of `workloads`
    cfg: dict               # the configuration's file
    mix: dict               # the traffic mix's file
    limits: dict            # name -> limit of each number compared
    seed: int
    seconds: float
    trace: bool
    t0: float               # time.perf_counter() at process start
    peaks: dict = None      # the chip's peaks (None off the chip)

    @property
    def chips(self):
        return self.workload["chips"]

    def trace_dir(self):
        """A fixed directory inside the checkout, emptied before use."""
        path = os.path.join(self.root, ".bench_trace", self.workload["name"])
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path


@dataclasses.dataclass
class Outcome:
    """A driver's result. `metrics` are the end-to-end values it measured;
    `records` is what the per-layer readers need of the run (counts of work
    in the traced window, counters of the program); `trace_dir` is where the
    profiler wrote, if it ran; `numbers` are the numbers compared."""
    attempted: int
    failed: int
    metrics: dict
    numbers: dict
    memory_peak_bytes: int
    records: dict = dataclasses.field(default_factory=dict)
    trace_dir: str = None


def layer_reader(root, name):
    """The reader of one per-layer metric: `benchmarks/layer_metrics/<name>.py`
    with a function `read(view)` that returns the value, or None where it
    finds nothing to read. A quantity that is read alike on every path keeps
    one reader: `device_idle.train` and `device_idle.serve` are both read by
    `device_idle.py`."""
    folder = os.path.join(root, "benchmarks", "layer_metrics")
    path = os.path.join(folder, f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(folder, f"{name.rsplit('.', 1)[0]}.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@dataclasses.dataclass
class View:
    """What a per-layer reader sees."""
    cfg: dict
    mix: dict
    peaks: dict
    chips: int
    records: dict
    window_s: float
    busy_s: float
    events: list            # per device plane, op events clipped to the window
