"""What one run is given and what it hands back, whatever the kind of mix."""
import dataclasses
import functools
import importlib.util
import os
import re
import shutil
import sys
import time

START = time.perf_counter()     # run.py moves it back to its own first line


def log(msg):
    """A line on standard error, stamped with the seconds since the process
    started: where set-up goes is read off these."""
    print(f"[bench {time.perf_counter() - START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Family:
    """What is one architecture's own, found by file: the directory
    `benchmarks/families/<model_type>/`, `model_type` being the key every
    configuration file carries. Two halves kept apart:

      model.py    the plain half: `leaves`, `layer_kinds`, `embed_leaves`,
                  `layer_leaves`, `head_leaves`, `embed`, `layer`, `head`,
                  `stack`, `leaf_names`, `parts`, `leaf_norms`,
                  `forward_bytes`
      counts.py   operations and bytes: `prompt_work`, `token_work`,
                  `kv_bytes_per_row`, `train_flops_per_token`, and the costs
                  of the family's own kernels
      program.py  the only file that imports the program: `build_model`,
                  `state_key`; loaded when first asked for, so the reference
                  and the readers never import the program

    A later PR adds a family as a directory and edits nothing."""
    MODEL = ("leaves", "layer_kinds", "embed_leaves", "layer_leaves",
             "head_leaves", "embed", "layer", "head", "stack", "leaf_names",
             "parts", "leaf_norms", "forward_bytes")
    COUNTS = ("prompt_work", "token_work", "kv_bytes_per_row",
              "train_flops_per_token")
    PROGRAM = ("build_model", "state_key")

    def __init__(self, root, model_type):
        self.name = model_type
        self.folder = os.path.join(root, "benchmarks", "families", model_type)
        if not os.path.isdir(self.folder):
            raise FileNotFoundError(
                f"no family {model_type!r}: {self.folder} is the directory a "
                f"configuration of that model_type needs")
        self.model = self._half("model", self.MODEL)
        self.counts = self._half("counts", self.COUNTS)

    def _half(self, part, contract):
        module = _load(os.path.join(self.folder, f"{part}.py"),
                       f"bench_family_{self.name}_{part}")
        missing = [f for f in contract if not callable(getattr(module, f, None))]
        if missing:
            raise AttributeError(f"{module.__file__} lacks {missing}")
        return module

    @functools.cached_property
    def program(self):
        return self._half("program", self.PROGRAM)


@functools.lru_cache(maxsize=None)
def load_family(root, model_type):
    """One Family a (checkout, model_type) in a process."""
    return Family(root, model_type)


HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))         # the checkout this harness is in


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
    family: Family = None   # the configuration's family, loaded from `root`

    def __post_init__(self):
        if self.family is None:
            self.family = load_family(self.root, self.cfg["model_type"])

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
    return _load(path, "layer_metric_" + name).read


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
    family: Family = None   # the configuration's (this checkout's, if unsaid)

    def __post_init__(self):
        if self.family is None and self.cfg.get("model_type"):
            self.family = load_family(HERE, self.cfg["model_type"])
