"""Plumbing of the chip benchmark: finding a cell's parts by name, the
device check, and the result line.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

  chipbench/configs/<config>.json    the network, its policy and its source
  chipbench/traffic/<traffic>.json   the mix; its ``kind`` names the runner
                                     (``chipbench/<kind>.py``) that reads it
  chipbench/limits/<cell>.json       each correctness number's limit
  chipbench/metrics/<metric>.py      ``read(run) -> float | None``
  chipbench/peaks.json               peaks by ``device_kind``
  chipbench/kernels.json             kernel event name -> kernel family
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
from typing import Optional

from chipbench.trace import Spans

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"chipbench: no such file {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"chipbench: no {what} named {name!r}")


def load_config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def load_limits(cell: str) -> dict:
    return _json(BENCH / "limits" / f"{cell}.json")


def load_metric(name: str):
    """The reader module of a per-layer metric."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"chipbench: no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(kind: str):
    """The general runner that reads traffic mixes of this ``kind``."""
    return importlib.import_module(f"chipbench.{kind}")


def family(cfg):
    """-> (program-side module, plain reference module) of the config."""
    fam = cfg["family"]
    return (importlib.import_module(f"chipbench.families.{fam}"),
            importlib.import_module(f"chipbench.families.{fam}_ref"))


def peak_for(kind: str) -> dict:
    """Peaks of one chip of this ``device_kind``; an unknown kind is an
    error, never a default."""
    peaks = _json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"chipbench: no peaks for device_kind {kind!r}; "
                       f"known: {sorted(peaks)}")
    return peaks[kind]


def kernel_families() -> dict:
    return _json(BENCH / "kernels.json")["families"]


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def metrics_of(bench: dict, cell: str, group: str):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"] if applies(m, cell)}
    out = []
    for m in bench[group]:
        if group == "per_layer":
            ok = (cell in m["workloads"]) if "workloads" in m else (
                m["moves"] in e2e)
        else:
            ok = applies(m, cell)
        if ok:
            out.append(m)
    return out


def check_devices(jax, chips: int):
    """-> (devices, platform, kind); raises :class:`NoChip`."""
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    print(f"[device] platform={platform} device_kind={kind!r} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips], platform, kind


@dataclasses.dataclass
class Run:
    """One run of one cell: what the runner was given and what it found.

    Runners fill ``e2e``, ``checks``, the counts, ``steps`` (the timed
    calls: ``(t0, t1, real_rows, executed_rows)`` on ``perf_counter``),
    ``spans`` (named host spans for the trace) and ``counters``; the trace
    reduction fills ``trace``.  Per-layer readers read it.
    """

    cell: dict
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    tracing: bool
    t_process: float
    devices: list = dataclasses.field(default_factory=list)
    peak: Optional[dict] = None
    e2e: dict = dataclasses.field(default_factory=dict)
    checks: dict = dataclasses.field(default_factory=dict)
    complete: bool = True          # every answer due came back
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    steps: list = dataclasses.field(default_factory=list)
    spans: Spans = dataclasses.field(default_factory=Spans)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: Optional[object] = None

    def check(self, name: str, value: float):
        """Record a compared number beside its limit."""
        self.checks[name] = (float(value), float(self.limits[name]))

    @property
    def correct(self) -> bool:
        return (self.complete and bool(self.checks)
                and all(v == v and v <= lim
                        for v, lim in self.checks.values()))


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip, where the backend reports them: the
    peak of the buffers in use and of the memory reserved for the compiled
    programs' temporaries, which the TPU runtime counts apart."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(st.get("peak_bytes_in_use", 0)
                     + st.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def result(run: Run, metrics: dict, device: dict,
           breakdown: Optional[dict]) -> str:
    """The last line of standard output; the compared numbers come last."""
    out = {"correct": run.correct, "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return json.dumps(out)
