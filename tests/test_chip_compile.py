"""The main QR path's Pallas kernels, compiled for a described TPU v5e.

Nothing runs here: each test compiles one kernel (``interpret=False``) at
the paper's per-rank widths for a chip that is described, not attached,
and checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  What the chip's compiler refuses — a slice not
aligned to the tiling, fast memory over the limit, a program larger than
the device — fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, so every test
worker must collect the same tests and only the one running this file
loads it.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import apply_right, combine_gram, fused_apply_gram, gram
from repro.kernels import trailing_update as tu
from repro.qr import QRConfig, factorize

HBM_BYTES = 16 << 30            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # the compiler logs nowhere
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES
    return compiled


@pytest.mark.parametrize("m,n", [(1 << 18, 32), (1 << 20, 128), (1000, 32)])
def test_gram_compiles(spec, m, n):
    _compile_mosaic(lambda a: gram.gram(a, interpret=False), spec(m, n))


def test_gram_bf16_compiles(spec):
    """bfloat16 operands take the MXU's native pass (Mosaic refuses the
    f32 precision setting for them)."""
    _compile_mosaic(
        lambda a: gram.gram(a, interpret=False),
        spec(1 << 18, 128, dtype=jnp.bfloat16),
    )


@pytest.mark.parametrize("want_q", [True, False])
@pytest.mark.parametrize("m,n", [(1 << 18, 32), (1 << 20, 128)])
def test_fused_apply_gram_compiles(spec, m, n, want_q):
    _compile_mosaic(
        lambda a, w: fused_apply_gram.fused_apply_gram(
            a, w, interpret=False, want_q=want_q
        ),
        spec(m, n), spec(n, n),
    )


def test_apply_right_compiles(spec):
    _compile_mosaic(
        lambda a, w: apply_right.apply_right(a, w, interpret=False),
        spec(1 << 18, 32), spec(32, 32),
    )


@pytest.mark.parametrize("n", [32, 128])
def test_combine_gram_compiles(spec, n):
    _compile_mosaic(
        lambda r1, r2: combine_gram.combine_gram(r1, r2, interpret=False),
        spec(n, n), spec(n, n),
    )


@pytest.mark.parametrize("nt,next_width", [(512, 128), (256, 128), (128, 0)])
def test_trailing_update_compiles(spec, nt, next_width):
    """Blocked QR at 1024 rows per rank, panel 128: every trailing width
    the 512-column matrix shrinks through."""
    _compile_mosaic(
        lambda a, q, w: tu.trailing_update(
            a, q, w, next_width=next_width, interpret=False
        ),
        spec(1024, nt), spec(1024, 128), spec(128, nt),
    )


@pytest.mark.parametrize(
    "m,n,b,next_width,dtype",
    [
        (1 << 18, 128, 32, 32, jnp.float32),    # 100 columns by panels of 32
        (1 << 18, 128, 32, 0, jnp.float32),
        (1024, 512, 128, 128, jnp.float32),
        (1024, 128, 32, 32, jnp.bfloat16),
    ],
)
def test_trailing_update_shift_compiles(spec, m, n, b, next_width, dtype):
    """The scan pipeline's update: a lane-offset read of the padded
    working matrix, written back shifted and aliased in place."""
    _compile_mosaic(
        lambda a, q, w: tu.trailing_update_shift(
            a, q, w, shift=b, next_width=next_width, interpret=False
        ),
        spec(m, n, dtype=dtype), spec(m, b, dtype=dtype),
        spec(b, n - b, dtype=dtype),
    )


def test_blocked_pipeline_program_compiles(spec):
    """The whole fault-free blocked QR at 2^20 × 100 over 4 simulated
    ranks, panels of 32: one program whose trailing updates shift the
    working matrix themselves — XLA neither slices the trailing block out
    nor pads the result back."""
    cfg = QRConfig(panel_width=32, use_pallas=True, interpret=False)
    compiled = _compile_mosaic(
        lambda a: factorize(a, cfg).r, spec(4, 1 << 18, 100)
    )
    text = compiled.as_text()
    assert "trailing_update_shift" in text
    moves = re.findall(r"= (f32\[[\d,]+\])\S* (?:slice|pad|concatenate)\(", text)
    assert not {"f32[4,262144,96]", "f32[4,262144,128]"} & set(moves)


def test_panel_cross_compiles(spec):
    _compile_mosaic(
        lambda a: tu.panel_cross(a, split=128, interpret=False),
        spec(1024, 512),
    )


def test_pad_cross_compiles(spec):
    _compile_mosaic(
        lambda a: tu.pad_cross(a, split=128, out_width=512, interpret=False),
        spec(1024, 512),
    )


def test_cqr2_tsqr_program_compiles(spec):
    """``QRConfig.interpret=False`` reaches the TSQR local QR, so the whole
    CholeskyQR2 TSQR at the PowerSGD panel row holds Mosaic kernels and
    fits one chip."""
    cfg = QRConfig(local_r="cqr2_pallas", interpret=False)
    _compile_mosaic(lambda a: factorize(a, cfg).r, spec(4, 1 << 20, 128))
