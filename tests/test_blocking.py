"""The analytical blocking model: paper Eq. 1/2 verbatim + TPU adaptation."""
import pytest

from repro.core.blocking import (CPU_HASWELL, TPU_V5E,
                                 choose_blocking, cpu_max_tile_elems,
                                 cpu_min_tile_elems, resident_bytes)
from repro.core.memory_model import ConvShape, bytes_overhead, overhead_table


def test_paper_eq1_eq2_haswell():
    # Paper §3.1.2: E >= N_vec * N_fma * L_fma ; E <= N_reg * N_vec
    assert cpu_min_tile_elems(CPU_HASWELL) == 8 * 2 * 5 == 80
    assert cpu_max_tile_elems(CPU_HASWELL) == 16 * 8 == 128
    # feasible: the register tile exists (min <= max) — the paper's premise
    assert cpu_min_tile_elems(CPU_HASWELL) <= cpu_max_tile_elems(CPU_HASWELL)


def test_tpu_blocking_lane_alignment():
    b = choose_blocking(hi=58, wi=58, ci=256, co=256, hf=3, wf=3)
    assert b.cob == 128                      # full lane width
    assert b.cib == 128
    assert b.tile_elems >= TPU_V5E.l_fma * TPU_V5E.n_vec  # adapted Eq. 1


def test_blocking_narrow_channels():
    b = choose_blocking(hi=224, wi=224, ci=3, co=64, hf=7, wf=7, stride=2)
    assert b.cib == 3                        # first conv layer: tiny Ci
    assert 64 % b.cob == 0


def test_blocking_vmem_pressure():
    # huge map: full-height tiles cannot fit; hob must shrink
    b = choose_blocking(hi=1024, wi=1024, ci=128, co=128, hf=3, wf=3)
    win_bytes = 1024 * 1024 * b.cib * 4
    assert 2 * win_bytes < TPU_V5E.vmem_bytes or b.hob < 1022


def test_blocking_wide_map_shrinks_wob():
    # single enormous row: hob bottoms out at 1, wob (2-D tiling) must engage
    b = choose_blocking(hi=5, wi=2 ** 17, ci=256, co=256, hf=3, wf=3,
                        cob=128, cib=128)
    wo = 2 ** 17 - 2
    assert b.wob < wo and wo % b.wob == 0
    assert (resident_bytes(b.hob, b.wob, b.cob, b.cib, 3, 3)
            <= TPU_V5E.vmem_bytes)


def test_overhead_table_alexnet():
    """Paper-workload accounting: im2col overhead >> 0, direct == 0."""
    conv2 = ConvShape("alexnet-conv2", n=1, hi=27, wi=27, ci=96, co=256,
                      hf=5, wf=5, pad=2)
    assert bytes_overhead(conv2, "direct") == 0
    im2col = bytes_overhead(conv2, "im2col")
    assert im2col == 27 * 27 * 5 * 5 * 96 * 4          # (Ho*Wo)x(Hf*Wf*Ci)
    assert bytes_overhead(conv2, "mec") < im2col
    rows = overhead_table([conv2])
    assert rows[0]["im2col_vs_base"] > 1.0             # overhead exceeds base


# ---------------------------------------------------------------------------
# the backend decides the machine model, interpret mode and the cache
# ---------------------------------------------------------------------------

class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_machine_model_comes_from_device_kind():
    from repro.core.backend import DEVICE_KINDS, machine_for_device
    assert machine_for_device(_Device("tpu", "TPU v5 lite")) is TPU_V5E
    assert TPU_V5E.source                # the peaks name their source
    assert all(m.vmem_limit_bytes and m.tile for m in DEVICE_KINDS.values())
    # off the TPU the kernels interpret the v5e launches
    assert machine_for_device(_Device("cpu", "cpu")) is TPU_V5E
    # an unknown TPU is an error, never a default
    with pytest.raises(ValueError, match="no machine model"):
        machine_for_device(_Device("tpu", "TPU v99"))


def test_interpret_follows_the_backend(monkeypatch):
    import jax
    from repro.core.backend import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        resolve_interpret(True)


def test_compile_cache_dir_from_env_or_fixed_path(monkeypatch):
    import jax
    from repro.utils.cache import CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert enable_compile_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == "/some/where"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CACHE_DIR)
        assert CACHE_DIR.name == ".jax_cache"
        assert (CACHE_DIR.parent / "src" / "repro").is_dir()   # the checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_padded_tiles_count_what_mosaic_allocates():
    from repro.core.blocking import tile_bytes
    # unpadded model: plain bytes; chip model: lanes to 128, rows to 8
    # 32-bit words (16 bf16 rows)
    assert tile_bytes((3, 3, 3), 4) == 27 * 4
    assert tile_bytes((3, 3, 3), 4, (8, 128)) == 3 * 8 * 128 * 4
    assert tile_bytes((7, 128), 2, (8, 128)) == 16 * 128 * 2
    # a narrow stem pencil costs whole lanes on the chip
    b = choose_blocking(225, 225, 3, 32, 3, 3, 2, machine=TPU_V5E)
    assert b.hob * b.wob <= TPU_V5E.max_tile_rows


# ---------------------------------------------------------------------------
# pointwise backward: the prologue dgrad takes every input-channel block
# into its grid step; the wgrad reads dz and carries no prologue
# ---------------------------------------------------------------------------

def _pw_dgrad(hw, ci, co, cb_in, cb_out, machine=TPU_V5E):
    from repro.core.blocking import (choose_pointwise_dgrad_blocking,
                                     pointwise_dgrad_resident_bytes)
    ciblk = ci // cb_in
    b = choose_pointwise_dgrad_blocking(hw, hw, ciblk, machine=machine,
                                        cob=cb_out, cib=cb_in,
                                        precision="bf16")
    resident = pointwise_dgrad_resident_bytes(b.hob, b.wob, b.cob, b.cib,
                                              ciblk, 2, 4, machine.tile)
    assert resident <= machine.vmem_bytes
    assert ciblk * b.hob * b.wob <= machine.max_tile_rows
    assert hw % b.hob == 0 and hw % b.wob == 0
    return b


@pytest.mark.parametrize("hw,c,cb", [(56, 96, 96), (28, 192, 96),
                                     (14, 384, 128), (7, 768, 128)])
def test_pointwise_dgrad_fits_convnext_pw1(hw, c, cb):
    """ConvNeXt-T's pw1 (C -> 4C): VMEM never shrinks the step; only the
    56x56 map's 3136 rows meet the row cap, which the prologue-free
    dgrad's tile meets too."""
    from repro.core.blocking import choose_pointwise_blocking
    b = _pw_dgrad(hw, c, 4 * c, cb, cb)
    plain = choose_pointwise_blocking(hw, hw, 4 * c, c, cob=cb, cib=cb,
                                      precision="bf16")
    assert (b.hob, b.wob) == (plain.hob, plain.wob)
    assert (b.hob, b.wob) == ((28, 56) if hw == 56 else (hw, hw))


@pytest.mark.parametrize("hw,ci,co,cb_in,cb_out", [
    (112, 32, 64, 32, 64), (56, 64, 128, 64, 128),
    (7, 512, 1024, 128, 128), (7, 1024, 1024, 128, 128)])
def test_pointwise_dgrad_fits_mobilenet(hw, ci, co, cb_in, cb_out):
    """MobileNet's large maps shrink rows to the cap and fit; its
    1024-channel 7x7 maps keep the whole map with 4 and 8 accumulators."""
    b = _pw_dgrad(hw, ci, co, cb_in, cb_out)
    rows = {112: 14 * 112, 56: 28 * 56}.get(hw, hw * hw)
    assert b.hob * b.wob == rows


def test_pointwise_dgrad_accumulators_shrink_rows():
    """The ``ciblk`` accumulators are what the step holds more of: on a
    machine where MobileNet's conv13 step fits with one input-channel
    block but not with eight, the chooser shrinks rows until it fits."""
    import dataclasses
    from repro.core.blocking import pointwise_dgrad_resident_bytes
    one = pointwise_dgrad_resident_bytes(7, 7, 128, 128, 1, 2, 4,
                                         TPU_V5E.tile)
    eight = pointwise_dgrad_resident_bytes(7, 7, 128, 128, 8, 2, 4,
                                           TPU_V5E.tile)
    assert one < eight
    tight = dataclasses.replace(TPU_V5E, vmem_bytes=(one + eight) // 2)
    b = _pw_dgrad(7, 1024, 1024, 128, 128, machine=tight)
    assert b.hob < 7
    # the row cap counts every accumulator's rows
    capped = dataclasses.replace(TPU_V5E, max_tile_rows=8 * 49 - 1)
    assert _pw_dgrad(7, 1024, 1024, 128, 128, machine=capped).hob < 7


@pytest.mark.parametrize("hw,cb_in,cb_out", [(56, 96, 96), (14, 128, 128),
                                             (7, 128, 128), (112, 32, 64)])
def test_pointwise_wgrad_holds_no_prologue(hw, cb_in, cb_out):
    """The wgrad reads the dgrad's dz: its step holds the x and cotangent
    tiles, the weight-gradient block and accumulator, and with a fused
    bias three [1, Cob] pencils — no z tile."""
    from repro.core.blocking import (choose_pointwise_wgrad_blocking,
                                     pointwise_wgrad_resident_bytes)
    with pytest.raises(TypeError):
        pointwise_wgrad_resident_bytes(hw, hw, cb_out, cb_in,
                                       fused_prologue=True)
    b = choose_pointwise_wgrad_blocking(hw, hw, cob=cb_out, cib=cb_in,
                                        precision="bf16", fused_bias=True)
    assert b.hob * b.wob <= TPU_V5E.max_tile_rows
    bare = pointwise_wgrad_resident_bytes(b.hob, b.wob, cb_out, cb_in, 2, 4,
                                          tile=TPU_V5E.tile)
    with_db = pointwise_wgrad_resident_bytes(b.hob, b.wob, cb_out, cb_in, 2,
                                             4, fused_bias=True,
                                             tile=TPU_V5E.tile)
    assert with_db - bare == 3 * 8 * 128 * 4
    assert with_db <= TPU_V5E.vmem_bytes
