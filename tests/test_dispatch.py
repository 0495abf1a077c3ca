"""The conv dispatch subsystem (DESIGN.md §12).

* table round-trip: tune -> persist -> reload gives identical routing;
* precedence: per-call override > table entry > analytical prior, with
  the table-fallback degradation when the checked-in winner misfits;
* the relocated VmemMisfitError chain: window -> stream -> raise, asked
  pre-launch by ``route_pallas`` and by ``decide`` over the Pallas set;
* equivalence sweep: routing changes never change numerics — the same
  impl chosen through different sources is bitwise identical, the two
  Pallas variants are bitwise identical to each other (§11), and every
  reference impl agrees to float tolerance;
* the checked-in ``dispatch_table.json`` covers the full CI matrix
  (shapes x dtypes x directions) with measured entries.
"""
import pathlib
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.blocking import MachineModel, TPU_V5E, VmemMisfitError
from repro.core.context import ConvContext
from repro.core.dispatch import (CANDIDATES, ConvDispatcher, DispatchKey,
                                 Impl, KernelRoute, PALLAS_IMPLS,
                                 probe_impl, prior_order, route_pallas,
                                 stream_flag)
from repro.nn.conv import BlockedConv2D
from repro.nn.module import init_tree

# window misfits / streamed fits (the test_conv_stream deep-pencil regime,
# under a distinct name so the registry entry is unambiguously this file's)
DEEP = MachineModel(name="dispatch-deep-pencil", n_vec=32, n_fma=1, l_fma=8,
                    n_reg=64, vmem_bytes=50_000)
# nothing fits: even the streamed floor blows a 2 KB budget at 32-pencils
TINY = MachineModel(name="dispatch-no-fit", n_vec=32, n_fma=1, l_fma=8,
                    n_reg=64, vmem_bytes=2_000)


def _key(direction="fwd", dtype="f32", machine=TPU_V5E, ci=4, co=8,
         hi=10, wi=10, stride=1, pad="SAME"):
    return DispatchKey.make(1, hi, wi, ci, co, 3, 3, stride, pad, dtype,
                            machine, direction)


def _deep_key(direction="fwd", dtype="f32", machine=DEEP):
    return DispatchKey.make(1, 6, 6, 32, 32, 3, 3, 1, 1, dtype, machine,
                            direction)


def _fake_timer():
    """Deterministic increasing 'times': first feasible candidate wins and
    the closure is never executed (routing logic only, no jit)."""
    state = {"n": 0}

    def timer(fn, *args, iters=3, **kw):
        state["n"] += 1
        return state["n"] * 1e-6

    return timer


def _entry(key, impl, times=None):
    return {"key": key.to_json(), "impl": impl, "source": "tuned",
            "times_us": times or {impl: 1.0}}


# ---------------------------------------------------------------------------
# precedence: override > table > prior
# ---------------------------------------------------------------------------

def test_prior_routes_fwd_to_jnp_off_tpu():
    disp = ConvDispatcher()
    dec = disp.decide(_key("fwd"))
    assert dec.source == "prior"
    if jax.default_backend() != "tpu":
        assert dec.impl is Impl.JNP


def test_prior_routes_backward_to_window():
    disp = ConvDispatcher()
    for direction in ("dgrad", "wgrad"):
        dec = disp.decide(_key(direction))
        assert (dec.source, dec.impl) == ("prior", Impl.WINDOW)


def test_table_beats_prior():
    key = _key("fwd")
    disp = ConvDispatcher(table={key.ident: _entry(key, "window")})
    dec = disp.decide(key)
    assert (dec.impl, dec.source) == (Impl.WINDOW, "table")
    # a different dtype is a different key -> still prior
    assert disp.decide(_key("fwd", dtype="bf16")).source == "prior"


def test_override_beats_table():
    key = _key("fwd")
    disp = ConvDispatcher(table={key.ident: _entry(key, "window")})
    dec = disp.decide(key, override="lax")
    assert (dec.impl, dec.source) == (Impl.LAX, "override")
    dec = disp.decide(key, override=Impl.JNP)
    assert (dec.impl, dec.source) == (Impl.JNP, "override")


def test_table_fallback_degrades_to_best_measured():
    # checked-in winner (window) misfits on the deep-pencil machine: the
    # dispatcher degrades inside the measured set instead of re-deriving
    key = _deep_key("fwd")
    disp = ConvDispatcher(table={key.ident: _entry(
        key, "window", times={"window": 10.0, "stream": 20.0, "jnp": 5.0})})
    dec = disp.decide(key, cob=32, cib=32)
    assert (dec.impl, dec.source) == (Impl.JNP, "table-fallback")
    # restricted to the Pallas family the only usable measured impl wins
    dec = disp.decide(key, candidates=PALLAS_IMPLS, cob=32, cib=32)
    assert (dec.impl, dec.source) == (Impl.STREAM, "table-fallback")


def test_explain_reports_candidates_and_source():
    key = _key("fwd")
    disp = ConvDispatcher(table={key.ident: _entry(
        key, "window", times={"window": 2.0, "jnp": 3.0})})
    info = disp.explain(key)
    assert info["key"] == key.ident
    assert (info["impl"], info["source"]) == ("window", "table")
    assert set(info["candidates"]) == {i.value for i in CANDIDATES["fwd"]}
    assert info["candidates"]["window"]["measured_us"] == 2.0
    assert info["candidates"]["window"]["feasible"]
    assert "resident_bytes" in info["candidates"]["stream"]


# ---------------------------------------------------------------------------
# the relocated misfit fallback chain
# ---------------------------------------------------------------------------

def test_route_pallas_window_when_it_fits():
    assert route_pallas("fwd", n=1, hi=12, wi=12, ci=4, co=8, hf=3, wf=3,
                        stride=1, machine=TPU_V5E, dtype=jnp.float32,
                        cob=8, cib=4) is False


def test_route_pallas_falls_back_to_stream():
    assert route_pallas("fwd", n=1, hi=8, wi=8, ci=32, co=32, hf=3, wf=3,
                        stride=1, machine=DEEP, dtype=jnp.float32,
                        cob=32, cib=32) is True


def test_route_pallas_raises_when_nothing_fits():
    with pytest.raises(VmemMisfitError, match="both Pallas variants"):
        route_pallas("fwd", n=1, hi=8, wi=8, ci=32, co=32, hf=3, wf=3,
                     stride=1, machine=TINY, dtype=jnp.float32,
                     cob=32, cib=32)


def test_decide_prior_follows_the_same_chain():
    key = _deep_key("fwd")
    dec = ConvDispatcher().decide(key, candidates=PALLAS_IMPLS,
                                  cob=32, cib=32)
    assert (dec.impl, dec.source) == (Impl.STREAM, "prior")
    assert dec.probes["window"]["feasible"] is False
    assert dec.probes["stream"]["feasible"] is True

    nofit = _deep_key("fwd", machine=TINY)
    with pytest.raises(VmemMisfitError, match="no feasible conv impl"):
        ConvDispatcher().decide(nofit, candidates=PALLAS_IMPLS,
                                cob=32, cib=32)


def test_kernel_route_legacy_knobs():
    key = _key("fwd")
    disp = ConvDispatcher()
    assert disp.kernel_route(key, stream=True) == KernelRoute(True, True,
                                                              True)
    assert disp.kernel_route(key, hso=2) == KernelRoute(True, True, True)
    passthrough = KernelRoute(fwd=False, dgrad=True, wgrad=None)
    assert disp.kernel_route(key, stream=passthrough) is passthrough
    resolved = disp.kernel_route(key, cob=8, cib=4)
    assert all(isinstance(stream_flag(resolved, d), bool)
               for d in ("fwd", "dgrad", "wgrad"))


def test_kernel_route_forward_pins_never_reach_backward_probes():
    # stride-2 layer with a pinned forward tile: ho=6 divides by hob=3, but
    # the dgrad extent is (6-1)*2+3 = 13, which 3 does NOT divide — the pin
    # must stay forward-only, like _conv_bwd's unpinned backward launches
    key = _key("fwd", ci=16, co=16, hi=12, wi=12, stride=2, pad="SAME")
    route = ConvDispatcher().kernel_route(key, cob=16, cib=16, hob=3, wob=6)
    assert all(isinstance(stream_flag(route, d), bool)
               for d in ("fwd", "dgrad", "wgrad"))


# ---------------------------------------------------------------------------
# table round-trip: tune -> persist -> reload -> identical routing
# ---------------------------------------------------------------------------

def test_tune_persist_reload_round_trip(tmp_path):
    path = tmp_path / "table.json"
    disp = ConvDispatcher(path=path)
    keys = [_key(d) for d in ("fwd", "dgrad", "wgrad")]
    for key in keys:
        dec = disp.tune(key, timer=_fake_timer())
        assert dec.source == "tuned"
        # every feasible candidate was timed (tiny shape: all of them)
        assert set(dec.times_us) == {i.value for i in
                                     CANDIDATES[key.direction]}
        assert disp.decide(key).source == "tuned"   # measured this process
    disp.save()

    reloaded = ConvDispatcher.from_file(path)
    for key in keys:
        dec = reloaded.decide(key)
        assert dec.source == "table"                # persisted, not re-tuned
        assert dec.impl is disp.decide(key).impl    # identical routing
        assert dec.times_us == disp.decide(key).times_us
    assert reloaded.to_json() == disp.to_json()


def test_from_file_rejects_schema_drift(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"schema": 999, "entries": {}}')
    with pytest.raises(ValueError, match="schema"):
        ConvDispatcher.from_file(path)


def test_tune_with_real_timer_measures_everything(tmp_path):
    # one real measurement pass end to end (jit + interpret-mode Pallas):
    # all three directions on one tiny shape, every candidate feasible
    disp = ConvDispatcher(path=tmp_path / "t.json")
    for direction in ("fwd", "dgrad", "wgrad"):
        key = _key(direction, hi=8, wi=8)
        dec = disp.tune(key, iters=1)
        assert set(dec.times_us) == {i.value for i in CANDIDATES[direction]}
        assert all(t > 0 for t in dec.times_us.values())
        assert dec.impl.value in dec.times_us


# ---------------------------------------------------------------------------
# equivalence sweep: routing must never change numerics
# ---------------------------------------------------------------------------

def _layer_and_operands():
    layer = BlockedConv2D(ci=4, co=8, hf=3, wf=3, stride=1, padding="SAME",
                          activation="relu", lane=4)
    params = init_tree(layer.specs(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 10, 10, 4)).astype(np.float32))
    from repro.core.layout import nhwc_to_blocked
    return layer, params, nhwc_to_blocked(x, layer.layout.cb_in)


def test_routing_source_never_changes_numerics():
    layer, p, xb = _layer_and_operands()
    y_override = layer(p, xb, context=ConvContext(impl="window"))
    # same impl arrived at through a table entry: bitwise identical (the
    # layer's key carries its fusion tag — a ReLU layer fuses act'(z))
    key = DispatchKey.make(2, 10, 10, 4, 8, 3, 3, 1, "SAME", "f32",
                           TPU_V5E, "fwd", fusion="dz")
    disp = ConvDispatcher(table={key.ident: _entry(key, "window")})
    y_table = layer(p, xb, context=ConvContext(dispatch=disp))
    np.testing.assert_array_equal(np.asarray(y_override),
                                  np.asarray(y_table))
    # §11 guarantee, now a routing property: window == stream bit for bit
    y_stream = layer(p, xb, context=ConvContext(impl="stream"))
    np.testing.assert_array_equal(np.asarray(y_override),
                                  np.asarray(y_stream))


@pytest.mark.parametrize("impl", ["jnp", "im2col", "lax"])
def test_reference_impls_agree(impl):
    layer, p, xb = _layer_and_operands()
    want = np.asarray(layer(p, xb, context=ConvContext(impl="window")))
    got = np.asarray(layer(p, xb, context=ConvContext(impl=impl)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_use_pallas_alias_removed():
    # the deprecated boolean is gone for good: impl=/dispatch= are the only
    # routing knobs (DESIGN.md §12) — a stale caller fails loudly, not
    # silently-ignored-kwarg quietly
    layer, p, xb = _layer_and_operands()
    with pytest.raises(TypeError):
        layer(p, xb, use_pallas=True)


def test_prior_order_prefers_direct():
    key = _key("dgrad")
    order = prior_order(key, CANDIDATES["dgrad"])
    assert order[0] is Impl.WINDOW
    assert Impl.IM2COL not in order
    fwd_order = prior_order(_key("fwd"), CANDIDATES["fwd"])
    if jax.default_backend() != "tpu":
        assert fwd_order[0] is Impl.JNP
    # measurement-only impls trail the prior's preferences
    assert set(fwd_order[-2:]) == {Impl.IM2COL, Impl.LAX}


def test_probe_reference_impls_always_feasible():
    key = _deep_key("fwd", machine=TINY)
    for impl in (Impl.JNP, Impl.IM2COL, Impl.LAX):
        assert probe_impl(key, impl)["feasible"]


# ---------------------------------------------------------------------------
# the checked-in table: CI matrix coverage
# ---------------------------------------------------------------------------

def _benchmarks():
    repo = pathlib.Path(__file__).resolve().parent.parent
    if str(repo) not in sys.path:
        sys.path.insert(0, str(repo))
    import benchmarks.tune_dispatch as td
    return td


def test_checked_in_table_covers_ci_matrix():
    td = _benchmarks()
    disp = ConvDispatcher.from_file(missing_ok=False)
    keys = td.tuned_keys()
    cover = disp.coverage(keys)
    assert cover["missing"] == []
    # keys of the CPU test machines are measured; TPU-named keys wait for
    # the chip (prior-seeded, "not measured")
    for key in keys:
        entry = disp.table[key.ident]
        assert DispatchKey.from_json(entry["key"]).ident == key.ident
        assert Impl(entry["impl"])       # coercible
        if key.machine in td.TPU_MACHINES:
            assert entry["source"] == "prior"
        else:
            assert entry["source"] == "tuned" and entry["times_us"]


def test_shipped_table_has_no_cpu_timed_tpu_entry():
    """Every time under a TPU machine name must come from the chip; none
    has been measured there yet, so no TPU-named entry carries a time."""
    td = _benchmarks()
    disp = ConvDispatcher.from_file(missing_ok=False)
    tpu = [e for e in disp.table.values()
           if e["key"]["machine"] in td.TPU_MACHINES]
    assert tpu
    for entry in tpu:
        assert entry["source"] == "prior", entry["key"]
        assert "times_us" not in entry


def test_tune_dispatch_refuses_tpu_times_off_tpu():
    td = _benchmarks()
    key = _key("fwd")                    # a tpu_v5e key
    disp = ConvDispatcher()
    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU the chip may time TPU-named keys")
    assert not td.measurable(key)
    with pytest.raises(ValueError, match="refusing to time"):
        td.tune_key(disp, key)
    assert key.ident not in disp.table
    # a CPU test machine's key may be timed anywhere
    assert td.measurable(_deep_key("fwd"))


def test_prior_entries_are_rederived_not_replayed():
    """A prior-seeded entry records coverage only: the decision is the
    running backend's prior, whatever impl the seeding machine wrote."""
    key = _key("dgrad")
    entry = {"key": key.to_json(), "impl": "jnp", "source": "prior"}
    dec = ConvDispatcher(table={key.ident: entry}).decide(key)
    assert (dec.source, dec.impl) == ("prior", Impl.WINDOW)
