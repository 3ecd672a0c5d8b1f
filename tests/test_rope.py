"""The placing of q and k heads (ops/rope.py): the two kernels, interpreted
on the CPU, against the decoder's own passes (``_head_norm`` then ``_rope``)
and against the same written out in float32, at the four rotary cells'
shapes cut to a few hundred positions; which shapes the kernels take; what
the backward keeps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_shuffling_data_loader_tpu.models import mellum
from ray_shuffling_data_loader_tpu.ops import rope

_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class _Case:
    """A cell's q or k heads: ``x`` (batch, seq, heads x dim), the first
    ``rotated`` of a head's ``dim`` rotated under ``config``'s tables for a
    ``full_attention`` layer, read twice where a row holds a sequence
    twice."""

    batch: int
    seq: int
    heads: int
    dim: int
    rotated: int
    normed: bool
    config: mellum.DecoderConfig
    twice: bool = False


def _config(tiny: mellum.DecoderConfig, dim: int) -> mellum.DecoderConfig:
    return dataclasses.replace(tiny, head_dim=dim)


#: By the cell whose layers they are. With the fixture's blocks of 64 rows
#: by 256 lanes every case is several row blocks and, but for the k heads,
#: several head groups.
_CASES = {
    # one row of 2 L positions, both copies at positions 0..L-1
    "sdar_q": _Case(1, 256, 4, 128, 128, True,
                    _config(mellum.sdar_tiny(), 128), twice=True),
    "sdar_k": _Case(1, 128, 2, 128, 128, True,
                    _config(mellum.sdar_tiny(), 128), twice=True),
    "mellum_q": _Case(2, 128, 4, 128, 128, False,
                      _config(mellum.mellum_tiny(), 128)),
    # half a head rotated under YaRN's tables, nine heads: groups of three
    "laguna_q": _Case(2, 128, 9, 128, 64, False,
                      _config(mellum.laguna_tiny(), 128)),
    "lfm2_q": _Case(2, 128, 8, 64, 64, True,
                    _config(mellum.lfm2_tiny(), 64)),
    # 96 positions: no multiple of the 64 a block may hold, three of 32
    "rows_off_the_block": _Case(1, 96, 4, 128, 128, True,
                                _config(mellum.lfm2_tiny(), 128)),
}


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(rope, "_BLOCK_ROWS", 64)
    monkeypatch.setattr(rope, "_BLOCK_LANES", 256)


def _operands(case: _Case, dtype, seed: int = 5):
    tables = (mellum._twice_rope_tables if case.twice
              else mellum._rope_tables)(case.config, mellum.FULL, case.seq)
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = (case.batch, case.seq, case.heads * case.dim)
    x = jax.random.normal(keys[0], shape, jnp.float32).astype(dtype)
    scale = (jax.random.uniform(keys[1], (case.dim,), minval=0.5, maxval=1.5)
             if case.normed else None)
    return x, scale, tables, jax.random.normal(keys[2], shape)


def _with_grads(place, x, scale, mix):
    """``(out, d x, d scale)`` of ``sum(place(x, scale) * mix)``; no
    ``d scale`` without a scale."""
    def loss(x, scale):
        out = place(x, scale)
        return jnp.sum(out.astype(jnp.float32) * mix), out

    wrt = (0, 1) if scale is not None else (0,)
    grads, out = jax.grad(loss, wrt, has_aux=True)(x, scale)
    return (out, *grads)


def _places(case: _Case, cos, sin):
    """The placing three ways: the kernels interpreted, the decoder's
    passes, the float32 form rounded once."""
    def kernels(x, scale):
        return rope.placed_in_vmem(x, scale, cos, sin, case.rotated, _EPS,
                                   True)

    def passes(x, scale):
        if scale is not None:
            x = mellum._head_norm(x, case.heads, scale, _EPS)
        return mellum._rope(x, case.heads, cos, sin, case.rotated)

    def plain(x, scale):
        return rope.placed_plain(x, case.heads, cos, sin, case.rotated,
                                 scale, _EPS)

    return kernels, passes, plain


def _steps_apart(got, want):
    """How many of bfloat16's values lie between each pair, as float32."""
    def ordered(a):
        bits = np.asarray(a).view(np.int16).astype(np.int32)
        return np.where(bits < 0, -(bits & 0x7FFF), bits)

    return np.abs(ordered(got) - ordered(want))


@pytest.mark.parametrize("name", list(_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernels_place_as_the_decoders_passes_do(dtype, name,
                                                     small_blocks):
    """The output and the gradients of ``x`` and of the scale by the two
    kernels. float32: the decoder's passes to rounding. bfloat16: the
    float32 form rounded once to one of bfloat16's steps (where terms
    cancel, to float32's rounding of the terms), and the decoder's passes,
    which round once more between norm and rotation, to a step of the
    largest value; the scale's gradient is a float32 sum either way."""
    case = _CASES[name]
    rows, lanes = rope._block(case.seq, case.heads * case.dim)
    assert case.seq // rows > 1 and lanes <= 384
    assert rope.place_takes(case.seq, case.heads * case.dim, case.dim,
                            case.rotated, dtype)
    x, scale, (cos, sin), mix = _operands(case, dtype)
    kernels, passes, plain = _places(case, cos, sin)
    got = _with_grads(kernels, x, scale, mix)
    by_passes = _with_grads(passes, x, scale, mix)
    once = _with_grads(plain, x, scale, mix)
    for g, w in zip(got, by_passes):
        assert g.shape == w.shape and g.dtype == w.dtype
    if dtype == jnp.float32:
        for g, w in zip(got, by_passes):
            np.testing.assert_allclose(g, w, rtol=2e-5,
                                       atol=2e-6 * float(jnp.max(jnp.abs(w))))
        return
    for g, w, p in zip(got[:2], once[:2], by_passes[:2]):
        largest = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        gap = np.abs(np.asarray(g.astype(jnp.float32) - w.astype(jnp.float32)))
        assert np.all((_steps_apart(g, w) <= 1) | (gap <= 1e-6 * largest))
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   p.astype(jnp.float32), rtol=0,
                                   atol=2 ** -7 * largest)
    for g, w in zip(got[2:], once[2:]):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("transposed", [False, True],
                         ids=["rotation", "its_transpose"])
@pytest.mark.parametrize("dim,rotated", [(128, 128), (128, 64), (64, 64),
                                         (64, 32)])
def test_two_rolls_against_the_tables_are_the_rotation(dim, rotated,
                                                       transposed):
    """``n * cos + sum(roll(n, shift) * table)`` over a 128-lane register
    against the rotation as a matrix a position, ``cos`` on the diagonal
    and ``+-sin`` half a rotated range off it: two heads of 64 side by
    side never read each other, the lanes past ``rotated`` pass, and a
    whole head of 128 needs one roll."""
    seq = 8
    angles = jax.random.uniform(jax.random.key(0), (seq, rotated // 2),
                                maxval=6.0)
    angles = jnp.concatenate([angles, angles], axis=-1)
    passed = ((0, 0), (0, dim - rotated))
    cos = jnp.pad(jnp.cos(angles), passed, constant_values=1.0)
    sin = jnp.pad(jnp.sin(angles), passed)
    half = rotated // 2
    lane = np.arange(dim)
    turn = np.zeros((dim, dim), np.float32)       # turned = n @ turn
    turn[lane[:half] + half, lane[:half]] = -1.0
    turn[lane[:half], lane[:half] + half] = 1.0
    matrix = (np.eye(dim)[None] * np.asarray(cos)[:, None, :]
              + turn[None] * np.asarray(sin)[:, None, :])   # (S, in, out)
    if transposed:
        matrix = matrix.transpose(0, 2, 1)
    n = jax.random.normal(jax.random.key(1), (seq, 128))
    want = np.einsum("shi,sio->sho", np.asarray(n).reshape(seq, -1, dim),
                     matrix).reshape(seq, 128)
    cos128, pairs = rope._tables(cos, sin, rotated, transposed)
    assert len(pairs) == (1 if rotated == 128 else 2)
    got = n * cos128 + sum(jnp.roll(n, shift, axis=1) * table
                           for shift, table in pairs)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_which_shapes_the_kernels_take(monkeypatch):
    takes = rope.place_takes
    # the cells': SDAR's and Mellum's q and k, Laguna's layers, LFM2's
    assert takes(16384, 32 * 128, 128, 128, jnp.bfloat16)
    assert takes(16384, 4 * 128, 128, 128, jnp.bfloat16)
    assert takes(8192, 48 * 128, 128, 64, jnp.bfloat16)
    assert takes(8192, 64 * 128, 128, 128, jnp.float32)
    assert takes(8192, 32 * 64, 64, 64, jnp.bfloat16)
    assert rope._block(16384, 32 * 128) == (512, 1024)
    assert rope._block(8192, 48 * 128) == (512, 1024)
    assert rope._block(8192, 9 * 128) == (512, 384)
    assert rope._block(96, 128) == (96, 128)
    # the tiny configurations' heads of 16 or 8, a head that is no
    # register or half of one, an odd head of 64 (half a register over),
    # a sequence of no whole strip, another dtype, an odd rotated range
    assert not takes(64, 4 * 16, 16, 16, jnp.float32)
    assert not takes(8192, 32 * 256, 256, 256, jnp.bfloat16)
    assert not takes(8192, 3 * 64, 64, 64, jnp.bfloat16)
    assert not takes(8200, 32 * 128, 128, 128, jnp.bfloat16)
    assert not takes(8192, 32 * 128, 128, 128, jnp.float16)
    assert not takes(8192, 32 * 128, 128, 0, jnp.bfloat16)
    assert not takes(8192, 32 * 128, 128, 63, jnp.bfloat16)
    # and nothing anywhere but on the chip
    args = (16384, 32 * 128, 128, 128, jnp.bfloat16)
    assert not rope.places_in_vmem(*args)
    monkeypatch.setattr(rope, "on_tpu", lambda: True)
    assert rope.places_in_vmem(*args)
    assert not rope.places_in_vmem(64, 4 * 16, 16, 16, jnp.float32)


@pytest.mark.parametrize("normed", [True, False], ids=["normed", "rotated"])
def test_the_backward_keeps_the_projection_alone(normed):
    """Of arrays the size of ``x`` the backward reads ``x`` itself, an
    argument (what the half's checkpoint keeps or makes again already),
    and without a norm not even that: no new bytes live from the forward
    to the backward."""
    case = _CASES["sdar_k"]
    x, scale, (cos, sin), _ = _operands(case, jnp.bfloat16)
    scale = scale if normed else None
    _, residuals = rope._placed_fwd(x, scale, cos, sin, case.rotated, _EPS,
                                    True)
    large = [leaf for leaf in jax.tree.leaves(residuals)
             if leaf.size >= x.size]
    assert len(large) == int(normed)
    assert all(np.array_equal(leaf, x) for leaf in large)


def test_two_mosaic_calls_a_placing_under_the_ropes_scope():
    """One ``pallas_call`` forward and one backward, both under
    ``rsdl.lm.rope`` inside a program of their own."""
    case = _CASES["sdar_k"]
    x, scale, (cos, sin), mix = _operands(case, jnp.bfloat16)
    kernels, _, _ = _places(case, cos, sin)
    assert rope.SCOPE == mellum.ROPE_SCOPE == "rsdl.lm.rope"

    def calls(jaxpr, scopes=()):
        for eqn in jaxpr.eqns:
            stack = scopes + (str(eqn.source_info.name_stack),)
            if eqn.primitive.name == "pallas_call":
                yield "/".join(stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, stack)

    forward = list(calls(jax.make_jaxpr(kernels)(x, scale).jaxpr))
    both = list(calls(jax.make_jaxpr(
        lambda x, scale: _with_grads(kernels, x, scale, mix))(
            x, scale).jaxpr))
    assert len(forward) == 1 and len(both) == 2
    assert all(rope.SCOPE in name for name in both)
