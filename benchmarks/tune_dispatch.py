"""Regenerate / verify the checked-in conv dispatch table (DESIGN.md §12).

The persistent table ``src/repro/configs/dispatch_table.json`` is the
measured tier of the conv dispatcher: every CI-benched shape — the pinned
``CI_SHAPES`` on the default machine plus the pathological deep-pencil
shape on its tiny ``MachineModel`` — is *tuned* (every feasible candidate
timed with ``benchmarks.timing.time_fn``, winner recorded with its full
measurement vector) across {f32, bf16} x {fwd, dgrad, wgrad}.  The
``cnn_zoo`` layers are too big to time on a CI runner, so they are
*prior-seeded*: the analytical blocking model's choice lands in the table
with ``source: "prior"`` and ``check_regression --dispatch-table`` reports
them as "untuned" without gating.

A time recorded under a TPU machine name is a chip time: off the TPU the
Pallas candidates run in interpret mode, so ``tune_key`` refuses to time a
TPU-named key there, and ``regenerate`` prior-seeds those keys instead
("not measured").  Keys of the CPU test machines (the deep-pencil model)
are timed anywhere.

Runnable (the ``-m`` form is required — relative imports):

    PYTHONPATH=src python -m benchmarks.tune_dispatch            # regenerate
    PYTHONPATH=src python -m benchmarks.tune_dispatch --check    # CI gate

``--check`` regenerates into memory and compares against the checked-in
file: schema drift or a missing expected entry FAILS (the table no longer
covers what CI benches); a changed winner is REPORTED but does not gate
(runner noise moves close races — the trajectory artifact records it).
``--out`` writes the regenerated table (in ``--check`` mode: the artifact
uploaded next to ``BENCH_ci.json``).
"""
from __future__ import annotations

import argparse
import sys

import jax

from repro.core.backend import DEVICE_KINDS
from repro.core.blocking import TPU_V5E
from repro.core.dispatch import (DIRECTIONS, ConvDispatcher, DispatchKey,
                                 default_table_path)

from .cnn_zoo import ZOO
from .fig_conv import CI_SHAPES, FUSION_SHAPES, STREAM_SHAPES

# The tuned tier's dtype sweep — matches the CI bench job's --dtype flags.
CI_DTYPES = ("f32", "bf16")

# Fused-key variants of the fusion smoke shapes (DESIGN.md §14): the fwd
# key carries the epilogue fusion (res / gap) and the backward keys the
# in-kernel act'(z) prologue, so the table distinguishes fused geometry
# from unfused (the probes account the extra resident operands).
FUSION_TAGS = {"smoke.res": "res+dz", "smoke.gap": "gap+dz"}


def tuned_keys(dtypes=CI_DTYPES):
    """Every key the table must carry a *measured* entry for: the benched
    (shape, machine) pairs x dtypes x all three directions."""
    pairs = [(s, TPU_V5E) for s in CI_SHAPES]
    pairs += [p for p in STREAM_SHAPES if p not in pairs]
    return [DispatchKey.from_shape(s, d, machine, direction)
            for s, machine in pairs
            for d in dtypes
            for direction in DIRECTIONS]


def prior_keys():
    """The cnn_zoo layers — plus the fused-key variants of the fusion smoke
    shapes: coverage without measurement (prior-seeded; the fused keys route
    through ``probe_impl``'s fusion-aware choosers, which is exactly the
    distinction the table must record)."""
    keys = [DispatchKey.from_shape(s, "f32", TPU_V5E, direction)
            for s in ZOO for direction in DIRECTIONS]
    keys += [DispatchKey.from_shape(s, d, TPU_V5E, direction,
                                    fusion=FUSION_TAGS[s.name])
             for s in FUSION_SHAPES
             for d in CI_DTYPES
             for direction in DIRECTIONS]
    return keys


# the machine names a time may only be recorded under on the chip
TPU_MACHINES = frozenset(m.name for m in DEVICE_KINDS.values())


def measurable(key: DispatchKey) -> bool:
    """Whether this process may record times for ``key``: a TPU-named key
    only on a TPU backend."""
    return key.machine not in TPU_MACHINES or jax.default_backend() == "tpu"


def tune_key(disp: ConvDispatcher, key: DispatchKey, iters: int = 3):
    """``disp.tune(key)``, refusing to write interpret-mode times under a
    TPU machine name."""
    if not measurable(key):
        raise ValueError(
            f"refusing to time {key.ident} on the {jax.default_backend()} "
            f"backend: {key.machine!r} names a TPU, and a time recorded "
            "under it must come from the chip")
    return disp.tune(key, iters=iters)


def regenerate(iters: int = 3, verbose: bool = True) -> ConvDispatcher:
    """Tune + prior-seed a fresh table in memory (nothing written).  Keys
    this backend may not time (``measurable``) are prior-seeded."""
    disp = ConvDispatcher(path=default_table_path())
    for key in tuned_keys():
        if not measurable(key):
            dec = disp.seed_prior(key)
            if verbose:
                print(f"prior  {key.ident}: {dec.impl.value}  "
                      "(not measured: needs the chip)")
            continue
        dec = tune_key(disp, key, iters=iters)
        if verbose:
            times = " ".join(f"{k}={v:.0f}us"
                             for k, v in sorted(dec.times_us.items()))
            print(f"tuned  {key.ident}: {dec.impl.value}  ({times})")
    for key in prior_keys():
        dec = disp.seed_prior(key)
        if verbose:
            print(f"prior  {key.ident}: {dec.impl.value}")
    return disp


def check(fresh: ConvDispatcher, path=None) -> int:
    """Gate the checked-in table against a fresh regeneration.

    Fails on schema drift (unreadable/old-schema file, entries missing
    required fields) and on expected entries the file does not carry.
    Winner drift between the file and the fresh measurement is printed as
    a note only — close races flip with runner noise.
    """
    path = path or default_table_path()
    try:
        checked_in = ConvDispatcher.from_file(path, missing_ok=False)
    except (FileNotFoundError, ValueError) as e:
        print(f"FAIL: dispatch table unusable: {e}")
        return 1

    failures, notes = [], []
    for ident, entry in sorted(checked_in.table.items()):
        missing = {"key", "impl", "source"} - entry.keys()
        if missing:
            failures.append(f"{ident}: entry missing fields {sorted(missing)}"
                            " (schema drift)")
    for ident, entry in sorted(fresh.table.items()):
        have = checked_in.table.get(ident)
        if have is None:
            failures.append(f"{ident}: expected entry missing from {path}")
            continue
        if have.get("impl") != entry["impl"]:
            notes.append(f"{ident}: winner {have.get('impl')} (checked in) "
                         f"vs {entry['impl']} (fresh measurement)")

    for n in notes:
        print(f"note: {n}")
    if failures:
        print(f"\n{len(failures)} dispatch-table failure(s):")
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print(f"\nok: {path} covers all {len(fresh.table)} expected entries")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="regenerate or verify the checked-in conv dispatch "
                    "table (src/repro/configs/dispatch_table.json)")
    ap.add_argument("--check", action="store_true",
                    help="regenerate in memory and gate the checked-in "
                         "table: schema drift / missing entries fail, "
                         "winner changes are reported only")
    ap.add_argument("--out", default=None,
                    help="write the regenerated table to this path "
                         "(default: the checked-in location; with --check "
                         "the checked-in file is never touched)")
    ap.add_argument("--iters", type=int, default=3,
                    help="timing iterations per candidate (median-of-k)")
    args = ap.parse_args(argv)

    disp = regenerate(iters=args.iters)
    if args.check:
        if args.out:
            disp.save(args.out)
            print(f"wrote regenerated table to {args.out}")
        return check(disp)
    path = disp.save(args.out)
    print(f"wrote {path} ({len(disp.table)} entries)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
