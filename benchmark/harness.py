"""What every cell shares: finding the cell's files by name, the set-up
clock, JAX's compile counters, the device record, the peak table, the
per-layer readers and the result line.

Nothing here names a configuration, a traffic mix, a metric or a kernel:
``BENCHMARK.json`` names them and the files under ``benchmark/`` hold them.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchFailure(Exception):
    """The run cannot give a result; the process exits non-zero with the
    message and prints no result line."""


def say(message: str) -> None:
    print(f"bench: {message}", flush=True)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ------------------------------------------------------------------ cells

class Cell:
    """One entry of ``workloads`` with its configuration and traffic files
    and the metrics ``BENCHMARK.json`` makes it report."""

    def __init__(self, name: str, root: str = ROOT, bench: dict | None = None):
        """`bench`: a BENCHMARK.json already read (tests hand in a tiny
        one whose files lie under `root`)."""
        bench = bench or load_json(root, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise BenchFailure(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{[w['name'] for w in bench['workloads']]}")
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        self.name, self.chips = name, int(entry["chips"])
        self.config = load_json(root, conf["file"])
        self.traffic = load_json(root, bench["paths"][0], "traffic",
                                 entry["traffic"] + ".json")
        self.limits = load_json(root, bench["paths"][0], "limits",
                                name + ".json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        mine = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if m["moves"] in mine
                          and name in m.get("workloads", [name])]


def runner_for(cell: Cell):
    """The traffic file's ``kind`` selects the runner module."""
    return importlib.import_module(
        f"benchmark.runners.{cell.traffic['kind']}")


def reference_for(cell: Cell):
    return importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")


def weights_for(cell: Cell):
    """The configuration file's ``architecture`` names the module under
    ``benchmark/weights/`` that lays out the seeded arrays and pours them
    into the program's parameter tree."""
    return importlib.import_module(
        f"benchmark.weights.{cell.config['architecture']}")


def seed_key(seed: int):
    """A key from any whole number the driver may pass (past 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


# ----------------------------------------------------------------- set-up

class SetupClock:
    """Process start to the first timed step, itemised."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self.phases: dict[str, float] = {}
        self.total: float | None = None

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t)

    def close(self, meter: "CompileMeter") -> float:
        """Call where the window opens."""
        self.total = time.perf_counter() - self.t0
        self.compile_s = meter.compile_s + meter.retrieve_s
        return self.total

    def report(self, meter: "CompileMeter") -> None:
        rest = self.total - sum(self.phases.values())
        items = ", ".join(f"{k} {v:.2f}" for k, v in self.phases.items())
        say(f"setup_s {self.total:.2f} = {items}, other {rest:.2f}")
        say(f"setup compile: {meter.line()}")


class CompileMeter:
    """What JAX's compile path reports: requests that consulted the
    persistent cache, hits, seconds compiling and retrieving.  `mark()`
    then `since_mark()` count compiles inside the measured window."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = self.compiles = 0
        self.compile_s = self.retrieve_s = 0.0
        self._mark = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, seconds, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
            self.compiles += 1
        elif name == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieve_s += seconds

    def mark(self) -> None:
        self._mark = self.compiles

    def since_mark(self) -> int:
        return self.compiles - self._mark

    def line(self) -> str:
        return (f"{self.requests} cache requests, {self.hits} hits, "
                f"{self.compiles} backend compiles or loads in "
                f"{self.compile_s:.2f}s, {self.retrieve_s:.2f}s retrieving")


# ----------------------------------------------------------------- device

def claim_devices(chips: int, allow_cpu: bool = False):
    """The cell's devices, or a failure naming the platform: a benchmark
    run never falls back to the CPU (tests pass `allow_cpu`)."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform == "cpu" and not allow_cpu:
        raise BenchFailure(
            f"JAX found no accelerator: platform is {dev.platform!r} "
            f"({dev.device_kind}); a benchmark run needs {chips} TPU "
            f"chip(s) and prints no device metric without them")
    if len(devices) < chips:
        raise BenchFailure(
            f"the cell needs {chips} chips; platform {dev.platform!r} "
            f"({dev.device_kind}) has {len(devices)}")
    return devices[:chips]


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = load_json(HERE, "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise BenchFailure(
            f"benchmark/peaks.json has no entry for device kind "
            f"{device_kind!r}; add one with its source")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest chip: the allocator's peak plus what
    the runtime reserved for the loaded programs' temporaries, which a
    TPU's ``peak_bytes_in_use`` leaves out (read on a v5e, PR 23: a train
    step whose compiled temporaries are 10.2 GiB showed 4.0 GiB in use and
    the rest under ``peak_bytes_reserved``).  0 where the backend reports
    nothing, which the CPU does not."""
    peak, parts = 0, (0, 0)
    for d in devices:
        stats = d.memory_stats() or {}
        used = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if used + reserved > peak:
            peak, parts = used + reserved, (used, reserved)
    say(f"memory peak {peak / 2 ** 30:.3f} GiB on the fullest chip = "
        f"{parts[0] / 2 ** 30:.3f} allocated + {parts[1] / 2 ** 30:.3f} "
        f"reserved for program temporaries")
    return peak


def device_record(devices, peak_bytes: int, trace: dict | None) -> dict:
    rec = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices),
           "memory_peak_bytes": peak_bytes}
    if trace is not None:
        rec["busy_s"], rec["window_s"] = trace["busy_s"], trace["window_s"]
    return rec


# ---------------------------------------------------------------- metrics

def cost_function(name: str):
    """``benchmark/costs/<name>.py::cost``: operations and bytes from
    shapes."""
    return importlib.import_module(f"benchmark.costs.{name}").cost


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric the cell reports, through the reader its own
    file names.  A reader that finds nothing returns None and the metric
    is left out of the line."""
    out = {}
    for entry in cell.per_layer:
        spec = load_json(HERE, "metrics", entry["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}").read
        value = reader(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def select_end_to_end(cell: Cell, values: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise BenchFailure(f"the {cell.traffic['kind']} runner gave no "
                               f"{m['name']} for {cell.name}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def print_checks(checks: list[dict]) -> bool:
    """Every number compared, beside its limit; True when all hold."""
    ok = True
    for c in checks:
        good = c["value"] <= c["limit"]
        ok = ok and good
        say(f"check {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})"
            f" {'ok' if good else 'FAILED'}"
            + (f" -- {c['note']}" if c.get("note") else ""))
    return ok


def result_line(result: dict) -> None:
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
