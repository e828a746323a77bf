"""Compile every Pallas entry of the main path for a DESCRIBED TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (``jax.experimental.topologies``).  These
cases hand each kernel entry its real GPT-2 124M shapes with
``interpret=False`` and require that it lowers and that the program holds
a ``tpu_custom_call`` — what interpret-mode equality tests cannot see.  A
compile that passes is not a run: ``chip_smoke.py`` is the run.

Nothing executes, so arguments are ``ShapeDtypeStruct``s pinned to the
described device.  The persistent compile cache is switched off around
the file: an entry written for a described chip cannot be read back
without one, and the next run would warn.
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from trustworthy_dl_tpu.ops import fused_dequant_matmul as dq
from trustworthy_dl_tpu.ops import fused_stats
from trustworthy_dl_tpu.ops import grouped_matmul as gm
from trustworthy_dl_tpu.ops import paged_attention as pa
# (``ops.flash_attention`` the attribute is the entry function, which
# shadows its submodule.)
from trustworthy_dl_tpu.ops.flash_attention import (
    _blocks_for,
    _flash_bwd,
    _flash_fwd,
)

# GPT-2 124M widths and the serve CLI's pool defaults (cli.py: 8 slots,
# block 16, KV in the model dtype = bf16).
H, DH, D, V = 12, 64, 768, 50257
SLOTS, BLOCK, MAX_SEQ, CHUNK = 8, 16, 1024, 64
V_PAD = -(-V // pa.TRUST_TILE) * pa.TRUST_TILE


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one chip of a described v5e 2x2; skip where this
    jaxlib cannot describe it."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(jitted, dev, *args, **static):
    """Lower ``jitted`` on shapes pinned to the described chip and
    compile; the program must contain a Mosaic kernel."""
    def pin(a):
        if isinstance(a, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev)
        return a

    compiled = jitted.lower(*map(pin, args), **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


LAYERS = 2


def _paged_args(program, kv_dtype, block=BLOCK, head_dim=DH, heads=H,
                max_seq=MAX_SEQ, q_dtype=jnp.bfloat16, slots=SLOTS,
                layers=LAYERS):
    """Argument shapes of ``_paged_attn_call`` (fused decode over every
    slot) or ``_paged_prefill_call`` (one slot's chunk) over the stacked
    pool, ``jmax`` a query tile of the program's own rule; the int8
    tier's scales are the rows' planes wave by wave, as ``_attend`` lays
    them (``_wave_planes``)."""
    nbps = max_seq // block
    nb = slots * nbps + 1
    pool = S((layers, nb, block, heads * head_dim), kv_dtype)
    r, t = (slots, pa.QROWS) if program == "decode" else (1, CHUNK)
    scale = None
    if jnp.dtype(kv_dtype) == jnp.int8:
        wave = min(nbps, pa._step_shape(
            program, heads=heads, head_dim=head_dim, block_size=block,
            kv_dtype=kv_dtype, t=t)[2])
        scale = jax.eval_shape(
            lambda s, tbl: pa._wave_planes(
                s, 0, tbl, wave,
                pa._copies_its_blocks(block, heads * head_dim)),
            S((layers, nb, block, heads), jnp.float32),
            S((r, nbps), jnp.int32))
    tiles = pa.grid_steps(program, r, heads, nbps, t, head_dim, block,
                          kv_dtype)[2]
    return (S((r, heads, t, head_dim), q_dtype), pool, pool, scale, scale,
            S((r, nbps), jnp.int32), S((r,), jnp.int32),
            S((r, tiles), jnp.int32), S((1,), jnp.int32))


_PAGED_CALL = {"decode": pa._paged_attn_call,
               "prefill": pa._paged_prefill_call}


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_attention_lowers(v5e, program, kv_dtype):
    """Paged decode and chunked prefill at the CLI's default pool
    geometry, for every KV storage dtype the CLI offers."""
    assert pa.supports_paged_attention(
        head_dim=DH, block_size=BLOCK, kv_dtype=kv_dtype, interpret=False,
        program=program, n_embd=D)
    _compile(_PAGED_CALL[program], v5e, *_paged_args(program, kv_dtype),
             interpret=False)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_attention_lowers_at_the_serving_cell(v5e, program, kv_dtype):
    """The benchmark's serving cell (GPT-2 large: 24 slots, 20 heads of
    64, blocks of 16, 1,024 positions, chunk 64): a step takes every head
    of a block, the prefill program the whole chunk besides."""
    rows, t = (24, 1) if program == "decode" else (1, CHUNK)
    assert pa.grid_steps(program, rows, 20, MAX_SEQ // BLOCK, t, DH, BLOCK,
                         kv_dtype) == (rows, 1, 1, MAX_SEQ // BLOCK)
    _compile(_PAGED_CALL[program], v5e,
             *_paged_args(program, kv_dtype, heads=20, slots=24),
             interpret=False)


def _serving_program(dev, program, kv_dtype):
    """``_paged_chunk_impl`` (one call's rows, a chunk of each of eight
    slots) or ``_paged_decode_impl`` at the serving cell's geometry (GPT-2
    large: 36 layers, 24 slots, 20 heads of 64, blocks of 16, chunk 64),
    the pool donated as on the chip, compiled for the described v5e."""
    from trustworthy_dl_tpu.models import generate as gen
    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import scheduler as sch
    from trustworthy_dl_tpu.serve.kv_slots import init_paged_pool

    cfg = gpt2.GPT2Config(n_layer=36, n_embd=1280, n_head=20)
    slots, nbps = 24, MAX_SEQ // BLOCK

    def pin(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    view = pin(jax.eval_shape(lambda: gen._decode_view(
        gpt2.init_params(jax.random.PRNGKey(0), cfg), cfg)))
    kv = pin(jax.eval_shape(
        lambda: init_paged_pool(cfg, slots * nbps, BLOCK, kv_dtype)))
    i32, f32 = jnp.int32, jnp.float32
    if program == "chunk":
        fn = sch._paged_chunk_impl
        rows = sch.chunk_call_rows(CHUNK, slots)
        rest = (S((rows, CHUNK), i32), S((rows, nbps), i32),
                S((rows,), i32), S((rows,), i32),
                S((rows, 2), jnp.uint32), S((rows,), f32),
                S((rows,), jnp.bool_))
    else:
        fn = sch._paged_decode_impl
        rest = (S((slots,), i32), S((slots, nbps), i32), S((slots,), i32),
                S((slots, 2), jnp.uint32), S((slots,), f32),
                S((slots,), jnp.bool_))
    jitted = jax.jit(fn, static_argnums=(0,),
                     static_argnames=("attn_impl", "adapter_impl"),
                     donate_argnums=(1, 2, 3, 4))
    return kv, _compile(jitted, dev, cfg, kv.k, kv.v, kv.k_scale,
                        kv.v_scale, view, *rest, attn_impl="pallas")


# (pool dtype, the most the program's temporaries may take).  The int8
# tier's scale planes [L, NB, BLOCK, H] are a sixty-fourth of the pool's
# elements and the one array here the compiler still relays out: the chip
# rests them block-index-minor with BLOCK on the sublanes, the row scatter
# wants the heads there, so each plane is copied whole before and after the
# layer loop (77 MB each: 93 MB of temporaries in a chunk program, of one
# row or of eight, 185 in the decode program) — PERF.md section 7 has what
# the alternatives cost on the chip.
_POOL_TIERS = [pytest.param(jnp.bfloat16, 64 << 20, id="bfloat16"),
               pytest.param(jnp.int8, 256 << 20, id="int8")]


@pytest.mark.parametrize("kv_dtype,temp_limit", _POOL_TIERS)
@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_serving_program_keeps_the_pool_in_place(v5e, program, kv_dtype,
                                                 temp_limit):
    """THE pin of the pool's shape ``[L, NB, BLOCK, H·Dh]``: a serving
    program carries the pool through its layer loop in the donated buffer
    and in one layout.  It compiles; its temporaries stay under 64 MB
    with the bf16 pool (6.0 GB when the layer scan re-stacked the pool;
    3.1 GB then with the int8 pool); both pools are updated in the
    buffers they came in; and the optimized HLO holds no copy, fresh
    buffer or slice the size of the pool or of one layer of it — the row
    write, the kernels' operand and the resting layout agree, so nothing
    relays the pool out."""
    import math
    import re

    kv, compiled = _serving_program(v5e, program, kv_dtype)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < temp_limit
    pool_bytes = math.prod(kv.k.shape) * jnp.dtype(kv_dtype).itemsize
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    sizes = {math.prod(kv.k.shape), math.prod(kv.k.shape[1:])}
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m or not m.group(2):
            continue
        name, dims, opcode = m.groups()
        if math.prod(map(int, dims.split(","))) not in sizes:
            continue
        if (opcode.startswith("copy") or "AllocateBuffer" in line
                or "slice" in opcode or "slice" in name):
            moved.append(line.strip()[:160])
    assert not moved, moved


# -- the second served architecture: grouped heads, state beside the pool ----

# 64 query heads over 8 K/V heads of 128, 64 slots of 8,192 positions in
# blocks of 64, chunks of 1,024 (benchmark/configs/solar-open2-...json).
GQ, GKV, GDH, GSLOTS, GBLOCK, GMAX, GCHUNK = 64, 8, 128, 64, 64, 8192, 1024


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_attention_lowers_at_grouped_heads(v5e, program):
    """The kernel with the 8 query heads of each K/V head as rows of its
    products: decode holds all 8 K/V heads (64 rows each) a step, a chunk
    goes a K/V head and 256 positions (2,048 rows) a step."""
    nbps = GMAX // GBLOCK
    rows, t = (GSLOTS, 1) if program == "decode" else (1, GCHUNK)
    grid = pa.grid_steps(program, rows, GQ, nbps, t, GDH, GBLOCK,
                         jnp.bfloat16, kv_heads=GKV)
    assert grid == ((GSLOTS, 1, 1, nbps) if program == "decode"
                    else (1, GKV, 4, nbps))
    rep = GQ // GKV
    t_pad = max(t, pa.QROWS)
    pool = S((1, GSLOTS * nbps + 1, GBLOCK, GKV * GDH), jnp.bfloat16)
    _compile(_PAGED_CALL[program], v5e,
             S((rows, GKV, rep * t_pad, GDH), jnp.bfloat16), pool, pool,
             None, None, S((rows, nbps), jnp.int32), S((rows,), jnp.int32),
             S((rows, grid[2]), jnp.int32), S((1,), jnp.int32),
             interpret=False, rep=rep)


def _decoder_program(dev, monkeypatch, program, family, config_name, slots,
                     block, max_seq, chunk):
    """One of the two scheduler programs for the ``DecoderConfig`` of a
    configuration of the benchmark at its published widths, pool and
    recurrent state donated as on the chip, compiled for the described
    chip: ``(compiled, pool, state)``."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from trustworthy_dl_tpu.models import decoder
    from trustworthy_dl_tpu.serve import scheduler as sch
    from trustworthy_dl_tpu.serve.kv_slots import (init_paged_pool,
                                                   init_state_pool)

    with open(os.path.join(root, "benchmark", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    cfg = family.model(config)
    nbps = max_seq // block

    def pin(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    view = pin(jax.eval_shape(lambda: decoder.decode_view(
        family.make_weights(0, config), cfg)))
    kv = pin(jax.eval_shape(
        lambda: init_paged_pool(cfg, slots * nbps, block, jnp.bfloat16)))
    state = pin(jax.eval_shape(lambda: init_state_pool(cfg, slots)))
    i32, f32 = jnp.int32, jnp.float32
    if program == "chunk":
        fn = sch._paged_chunk_impl
        rest = (S((1, chunk), i32), S((1, nbps), i32), S((1,), i32),
                S((1,), i32), S((1, 2), jnp.uint32), S((1,), f32),
                S((1,), jnp.bool_))
        extra = dict(state=state, slot=pin(S((1,), i32)))
    else:
        fn = sch._paged_decode_impl
        rest = (S((slots,), i32), S((slots, nbps), i32), S((slots,), i32),
                S((slots, 2), jnp.uint32), S((slots,), f32),
                S((slots,), jnp.bool_))
        extra = dict(state=state, active=pin(S((slots,), jnp.bool_)))
    jitted = jax.jit(fn, static_argnums=(0,),
                     static_argnames=("attn_impl", "adapter_impl"),
                     donate_argnums=(1, 2, 3, 4), donate_argnames=("state",))
    # The dispatch predicates ask the backend; the trace is for the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(jitted, dev, cfg, kv.k, kv.v, None, None, view,
                        *rest, attn_impl="pallas", **extra)
    return compiled, kv, state


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_decoder_serving_program_lowers_in_place(v5e, monkeypatch, program):
    """Both scheduler programs for the ``DecoderConfig`` of the benchmark's
    configuration at its published widths, pool and recurrent state donated
    as on the chip: they compile, hold the paged kernel and the grouped
    products' kernel (two calls a layer, none of XLA's ``ragged-dot``),
    update pool AND state in the buffers they came in, and keep
    their temporaries under 1 GB (the chunk's activations; a copy of the
    state of one layer alone would be 0.27 GB, of the pool 2.1 GB)."""
    from benchmark.harness.families import solar_open2

    compiled, kv, state = _decoder_program(
        v5e, monkeypatch, program, solar_open2, "solar-open2-250b-ep8-1of8",
        GSLOTS, GBLOCK, GMAX, GCHUNK)
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert text.count("_gmm_call") >= 2
    memory = compiled.memory_analysis()
    carried = kv.k.size * 2 * 2 + state.s.size * 4 + state.conv.size * 4
    assert memory.alias_size_in_bytes >= carried
    assert memory.temp_size_in_bytes < 1 << 30
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 12 << 30                      # of the chip's 16 GB


# The latent cache at the long-context cell's geometry: 32 query heads over
# ONE shared row of 576 values in 640 lanes whose first 512 are the values.
LHEADS, LLANES, LVALUES = 32, 640, 512
LSLOTS, LBLOCK, LMAX, LCHUNK = 64, 256, 32768, 1024


def _latent_decode(dev, lanes):
    nbps = LMAX // LBLOCK
    grid = pa.grid_steps("decode", LSLOTS, LHEADS, nbps, 1, lanes, LBLOCK,
                         jnp.bfloat16, kv_heads=1, v_lanes=LVALUES)
    pool = S((1, LSLOTS * nbps + 1, LBLOCK, lanes), jnp.bfloat16)
    compiled = _compile(
        pa._latent_decode_call, dev,
        S((LSLOTS, 1, LHEADS, lanes), jnp.bfloat16), pool,
        S((LSLOTS, nbps), jnp.int32), S((LSLOTS,), jnp.int32),
        S((LSLOTS, grid[2]), jnp.int32), S((1,), jnp.int32),
        interpret=False, rep=LHEADS, v_lanes=LVALUES, scale=192 ** -0.5)
    return grid, pool, compiled


def test_paged_attention_lowers_in_the_latent_shape(v5e):
    """The ONE paged kernel with no V operand: a decode call's tile is the
    32 heads of one position; the pool of 640 lanes is read where it lies
    (no temporary), where one of 576 lanes (which the step cannot copy out
    of HBM itself, so the grid walks it) is copied WHOLE into a padded
    layout first: why the pool's rows are padded to whole lane columns."""
    grid, pool, compiled = _latent_decode(v5e, LLANES)
    assert grid == (LSLOTS, 1, 1, 128)
    assert "latent_decode" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert pa._copies_its_blocks(LBLOCK, LLANES)
    assert not pa._copies_its_blocks(LBLOCK, 576)
    _, narrow, copied = _latent_decode(v5e, 576)
    assert copied.memory_analysis().temp_size_in_bytes >= narrow.size * 2


def test_latent_prefill_lowers_at_the_cell_geometry(v5e):
    """The chunk's expanded kernel: 2 heads' 1,024 queries resident, a grid
    of 16 head groups x 128 blocks, the pool read where it lies, inside the
    scoped VMEM limit; the predicate admits it."""
    from trustworthy_dl_tpu.ops import latent_attention as la

    shape = dict(nope=128, value=128, rank=LVALUES, lanes=LLANES,
                 block_size=LBLOCK, dtype=jnp.bfloat16)
    assert la.supports_latent_prefill(heads=LHEADS, rows=LCHUNK,
                                      interpret=False, **shape)
    assert la.head_group(LHEADS, LCHUNK, **shape) == 2
    nbps = LMAX // LBLOCK
    compiled = _compile(
        la._latent_prefill_call, v5e,
        S((LHEADS, LCHUNK, 128 + LLANES - LVALUES), jnp.bfloat16),
        S((LVALUES, LHEADS * 256), jnp.bfloat16),
        S((1, LSLOTS * nbps + 1, LBLOCK, LLANES), jnp.bfloat16),
        S((1, nbps), jnp.int32), S((1,), jnp.int32), S((1,), jnp.int32),
        S((1,), jnp.int32), interpret=False, nope=128)
    assert "latent_prefill" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_latent_serving_program_lowers_in_place(v5e, monkeypatch, program):
    """Both scheduler programs for the long-context configuration at its
    published widths: they compile with the latent kernel under its own
    name; the pool is ONE array of 640-lane rows with no V half, written
    in place and read through the layer index (nothing of the pool's shape
    is produced but the parameter and its bitcast); pool and state are
    updated in the buffers they came in; NO per-head K or V of the cached
    positions exists among the arguments or the temporaries (one slot's at
    32k positions would be 0.67 GB, the pool's 43 GB)."""
    import re

    from benchmark.harness.families import kimi_linear

    compiled, kv, state = _decoder_program(
        v5e, monkeypatch, program, kimi_linear, "kimi-linear-48b-ep2-1of2",
        LSLOTS, LBLOCK, LMAX, LCHUNK)
    assert kv.v is None
    assert kv.k.shape == (1, LSLOTS * 128 + 1, LBLOCK, LLANES)
    text = compiled.as_text()
    kernel = "latent_prefill" if program == "chunk" else "latent_decode"
    assert kernel in text and "_paged_attn_call" not in text
    assert "ragged-dot" not in text and text.count("_gmm_call") >= 2
    shape = re.escape("bf16[%d,%d,%d,%d]" % kv.k.shape)
    made = set(re.findall(shape + r"\S* ([a-z\-]+)\(", text))
    assert made <= {"parameter", "bitcast"}, made
    # No array over the pool's blocks but the pool itself.
    assert not re.findall(r"\[(?:\d+,)*%d,%d,(?!%d\])\d+\]" % (
        kv.k.shape[1], LBLOCK, LLANES), text)
    memory = compiled.memory_analysis()
    carried = kv.k.size * 2 + state.s.size * 4 + state.conv.size * 4
    assert memory.alias_size_in_bytes >= carried
    assert memory.temp_size_in_bytes < 512 << 20
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 13 << 30                      # of the chip's 16 GB


# -- the hybrid layer: rotary attention at 5 query heads a K/V head beside a
# Mamba-2 state of [32, 128, 256] a slot (benchmark/configs/falcon-h1-...).
FQ, FKV, FDH = 20, 4, 128


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_attention_lowers_at_five_heads_a_group(v5e, program):
    """The ONE paged kernel with the 5 query heads of each K/V head as rows
    of its products (no cell ran a group of 5 before), both shapes, at the
    hybrid cell's pool: 64 slots of 8,192 positions in blocks of 64."""
    nbps = GMAX // GBLOCK
    rows, t = (GSLOTS, 1) if program == "decode" else (1, GCHUNK)
    grid = pa.grid_steps(program, rows, FQ, nbps, t, FDH, GBLOCK,
                         jnp.bfloat16, kv_heads=FKV)
    assert pa.supports_paged_attention(
        head_dim=FDH, block_size=GBLOCK, kv_dtype=jnp.bfloat16,
        interpret=False, program=program, n_embd=FKV * FDH)
    rep = FQ // FKV
    t_pad = max(t, pa.QROWS)
    pool = S((1, GSLOTS * nbps + 1, GBLOCK, FKV * FDH), jnp.bfloat16)
    _compile(_PAGED_CALL[program], v5e,
             S((rows, FKV, rep * t_pad, FDH), jnp.bfloat16), pool, pool,
             None, None, S((rows, nbps), jnp.int32), S((rows,), jnp.int32),
             S((rows, grid[2]), jnp.int32), S((1,), jnp.int32),
             interpret=False, rep=rep)


def test_the_ssm_step_kernel_lowers_in_place(v5e):
    """The Mamba-2 decode step over a stacked state of 4 layers x 64 slots:
    it compiles under its own name, updates the state in the buffer it came
    in (aliased, donated) and needs no temporary the size of a layer's
    state (268 MB)."""
    from trustworthy_dl_tpu.ops import ssm_step as ss

    state = S((4, GSLOTS, 32, 128, 256), jnp.float32)
    jitted = jax.jit(ss.ssm_step, static_argnames=("interpret",),
                     donate_argnums=(0,))
    compiled = _compile(
        jitted, v5e, state, S((), jnp.int32),
        S((GSLOTS, 32, 128), jnp.float32), S((GSLOTS, 32), jnp.float32),
        S((32,), jnp.float32), S((GSLOTS, 2, 256), jnp.float32),
        S((GSLOTS, 2, 256), jnp.float32), S((32,), jnp.float32),
        interpret=False)
    assert "ssm_step" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= state.size * 4
    assert memory.temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_hybrid_serving_program_lowers_in_place(v5e, monkeypatch, program):
    """Both scheduler programs for the Falcon-H1 configuration at its
    published widths, pool and Mamba-2 state donated as on the chip: they
    compile with the paged kernel (and, in a decode step, ``ssm_step``);
    pool AND state are updated in the buffers they came in; no copy of the
    state, of a layer of it or of the pool exists (the state of all layers
    is produced only by its parameter, the kernel or an in-place update);
    arguments and temporaries stay under 15.5 GB of the chip's 16 GiB."""
    import re

    from benchmark.harness.families import falcon_h1

    compiled, kv, state = _decoder_program(
        v5e, monkeypatch, program, falcon_h1, "falcon-h1-34b-pp18-1of18",
        GSLOTS, GBLOCK, GMAX, GCHUNK)
    assert state.s is None and state.ssm.shape == (4, GSLOTS, 32, 128, 256)
    text = compiled.as_text()
    if program == "decode":
        assert "ssm_step" in text and "_paged_attn_call" in text
    else:
        assert "_paged_prefill_call" in text and "ssm_step" not in text
    for shape in (state.ssm.shape, state.ssm.shape[1:]):
        made = set(re.findall(re.escape("f32[%s]" % ",".join(
            map(str, shape))) + r"\S* ([a-z\-]+)\(", text))
        assert made <= {"parameter", "get-tuple-element", "custom-call",
                        "dynamic-update-slice", "fusion", "bitcast"}, made
        assert "copy" not in made
    memory = compiled.memory_analysis()
    carried = kv.k.size * 2 * 2 + state.ssm.size * 4 \
        + state.ssm_conv.size * 4
    assert memory.alias_size_in_bytes >= carried
    assert memory.temp_size_in_bytes < 256 << 20
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.5e9


def _flash_forward(dev, dtype, bh=H, t=1024, d=DH, causal=True):
    q = S((bh, t, d), dtype)
    _compile(_flash_fwd, dev, q, q, q, causal=causal,
             blocks=_blocks_for(t, d), interpret=False)


def _flash_backward(dev, dtype, bh=H, t=1024, d=DH, causal=True):
    q = S((bh, t, d), dtype)
    _compile(_flash_bwd, dev, q, q, q, q, S((bh, t), jnp.float32), q,
             causal=causal, blocks=_blocks_for(t, d), interpret=False)


def _fused_moments(dev):
    _compile(fused_stats._fused_tile_moments, dev,
             S((4 * fused_stats.BLOCK_ROWS, fused_stats.LANES),
               jnp.float32), interpret=False)


def _dequant_matmul(dev):
    # The decode MLP up-projection: [slots, D] @ int8 [D, 4D].
    _compile(dq._dq_matmul_pallas, dev, S((SLOTS, D), jnp.float32),
             S((D, 4 * D), jnp.int8), S((4 * D,), jnp.float32),
             interpret=False)


def _trust_epilogue(dev):
    _compile(pa._trust_stats_call, dev, S((SLOTS, V_PAD), jnp.float32),
             interpret=False)


def _verify_tail(dev):
    # spec_k=4 over every slot: 8 x 5 verify rows.
    _compile(pa._verify_tail_call, dev, S((SLOTS * 5, D), jnp.bfloat16),
             S((V_PAD, D), jnp.bfloat16), V, interpret=False,
             round_to="bfloat16")


def _adapter_delta(dev, rank, pool_dtype):
    pages = 5
    _compile(pa._adapter_delta_call, dev, S((SLOTS, pa.QROWS, D),
                                            jnp.bfloat16),
             S((pages, D, rank), pool_dtype),
             S((pages, rank, D), pool_dtype), S((SLOTS,), jnp.int32),
             S((SLOTS,), jnp.float32), S((SLOTS,), jnp.float32),
             interpret=False)


@pytest.mark.parametrize("entry", [
    pytest.param(lambda d: _flash_forward(d, jnp.bfloat16),
                 id="flash-fwd-bf16"),
    pytest.param(lambda d: _flash_forward(d, jnp.float32),
                 id="flash-fwd-f32"),
    pytest.param(lambda d: _flash_backward(d, jnp.bfloat16),
                 id="flash-bwd-bf16"),
    pytest.param(lambda d: _flash_backward(d, jnp.float32),
                 id="flash-bwd-f32"),
    # The benchmark cell's own call (8 rows x 12 heads a layer: one
    # [1024, 1024] tile walked in sub-tiles), the long shape (eight tiles a
    # side: carried accumulators, tile-level skip), no diagonal, and a
    # head as wide as the lanes in f32 (the fullest VMEM the tile rule
    # admits at this length).
    pytest.param(lambda d: _flash_forward(d, jnp.bfloat16, bh=96),
                 id="flash-fwd-cell-96x1024x64"),
    pytest.param(lambda d: _flash_backward(d, jnp.bfloat16, bh=96),
                 id="flash-bwd-cell-96x1024x64"),
    pytest.param(lambda d: _flash_forward(d, jnp.bfloat16, bh=12, t=8192),
                 id="flash-fwd-long-12x8192x64"),
    pytest.param(lambda d: _flash_backward(d, jnp.bfloat16, bh=12, t=8192),
                 id="flash-bwd-long-12x8192x64"),
    pytest.param(lambda d: _flash_forward(d, jnp.bfloat16, t=2048,
                                          causal=False),
                 id="flash-fwd-full-2048"),
    pytest.param(lambda d: _flash_backward(d, jnp.bfloat16, t=2048,
                                           causal=False),
                 id="flash-bwd-full-2048"),
    pytest.param(lambda d: _flash_backward(d, jnp.float32, bh=4, t=2048,
                                           d=128),
                 id="flash-bwd-f32-2048x128"),
    pytest.param(_fused_moments, id="fused-moments"),
    pytest.param(_dequant_matmul, id="dequant-matmul"),
    pytest.param(_trust_epilogue, id="trust-epilogue"),
    pytest.param(_verify_tail, id="verify-tail"),
    pytest.param(lambda d: _adapter_delta(d, 8, jnp.bfloat16),
                 id="adapter-delta-r8"),
    pytest.param(lambda d: _adapter_delta(d, 16, jnp.int8),
                 id="adapter-delta-r16-int8"),
])
def test_kernel_entry_lowers(v5e, entry):
    """The other kernels the trainer and the server reach, at GPT-2 124M
    shapes: flash forward/backward at T=1024, the detector's moments
    tile, the int8 dequant-matmul, the trust epilogue, the speculative
    verify tail and the adapter gather."""
    entry(v5e)


#: The held experts of the benchmark's second configuration: 40 of
#: ``[4096, 2 x 1280]`` and ``[1280, 4096]``, 8 pairs a token.
GM_HELD, GM_D, GM_F, GM_K = 40, 4096, 1280, 8


@pytest.mark.parametrize("product", ["gate-up", "down"])
@pytest.mark.parametrize("tokens", [GSLOTS, GCHUNK],
                         ids=["decode-512-rows", "chunk-8192-rows"])
def test_grouped_products_lower_at_the_cell_geometry(v5e, tokens, product):
    """Both grouped products of ``held_experts`` at the tiling the rule
    gives a decode call's 512 sorted rows (tiles of 16) and a chunk call's
    8,192 (tiles of 128): the double-buffered weights tile, the rows' and
    the output's blocks and the accumulator fit what a kernel may use."""
    m = tokens * GM_K
    k, n = (GM_D, 2 * GM_F) if product == "gate-up" else (GM_F, GM_D)
    tiles = gm.tiling(m, k, n, GM_HELD, 2)
    assert tiles[0] == (16 if tokens == GSLOTS else 128)
    tm, tk, tn = tiles
    pinned = 2 * (tk * tn * 2 + tm * tk * 2 + tm * tn * 4) + tm * tn * 4
    assert 2 * tk * tn * 2 <= pa.VMEM_BLOCK_BUDGET
    assert pinned < pa.VMEM_LIMIT_BYTES
    _compile(gm._gmm_call, v5e, S((m, k), jnp.bfloat16),
             S((GM_HELD, k, n), jnp.bfloat16), S((GM_HELD,), jnp.int32),
             tiles=tiles, interpret=False)


# Geometries off the dtype's sublane, off the 128 lanes, and large: what
# the predicate admits must lower — the old rule refused most of these.
# (program, pool dtype, block, head width[, heads]).  The large blocks
# leave room for one head a step or for a proper divisor of the twelve;
# the last three fill the budget from the other side, with q, out and the
# scratch of many wide heads (groups of 32, 16 and 32 at 7.5, 7.5 and 7.0
# of the 8 MiB).
_ADMITTED = [
    ("decode", jnp.bfloat16, 8, 64), ("prefill", jnp.int8, 16, 64),
    ("decode", jnp.float32, 12, 80), ("prefill", jnp.bfloat16, 32, 128),
    ("decode", jnp.int8, 4096, 128), ("prefill", jnp.float32, 1024, 256),
    ("decode", jnp.float32, 1024, 64), ("prefill", jnp.float32, 1024, 64),
    ("prefill", jnp.bfloat16, 16, 128, 64),
    ("prefill", jnp.float32, 16, 128, 96),
    ("prefill", jnp.int8, 32, 128, 64),
]
_REFUSED = [
    ("decode", jnp.float32, 4096, 512), ("prefill", jnp.bfloat16, 8192, 512),
]
# Pools the step cannot copy out of HBM itself, so that the grid walks
# them: a block off the 8 sublanes, rows off the 128 lanes (12 heads of 80,
# 25 of 64, the 3 of 64 a narrow tensor-parallel shard keeps), with the int8
# tier's planes among them; and beside them blocks of 24 out of rows of 640
# lanes, which the step walks three to the wave's tile.
_GRID_WALKED = [
    ("decode", jnp.float32, 12, 80), ("prefill", jnp.bfloat16, 16, 80),
    ("decode", jnp.bfloat16, 16, 64, 25), ("prefill", jnp.int8, 16, 64, 3),
    ("decode", jnp.int8, 32, 80),
]
_STEP_WALKED = [("decode", jnp.float32, 24, 80, 8),
                ("prefill", jnp.int8, 32, 64, 6)]


def _geometry_id(case):
    program, dtype, block, head_dim, *heads = case
    return (f"{program}-{jnp.dtype(dtype).name}-b{block}-d{head_dim}"
            + "".join(f"-h{h}" for h in heads))


@pytest.mark.parametrize("case", _ADMITTED, ids=_geometry_id)
def test_supported_geometry_lowers(v5e, case):
    program, kv_dtype, block, head_dim, *heads = case
    (heads,) = heads or (H,)
    assert pa.supports_paged_attention(
        head_dim=head_dim, block_size=block, kv_dtype=kv_dtype,
        interpret=False, program=program, n_embd=heads * head_dim)
    _compile(_PAGED_CALL[program], v5e,
             *_paged_args(program, kv_dtype, block, head_dim, heads=heads,
                          max_seq=max(MAX_SEQ, block)), interpret=False)


@pytest.mark.parametrize("case", _REFUSED, ids=_geometry_id)
def test_refused_geometry_is_refused_by_both(v5e, case):
    program, kv_dtype, block, head_dim = case
    assert not pa.supports_paged_attention(
        head_dim=head_dim, block_size=block, kv_dtype=kv_dtype,
        interpret=False, program=program, n_embd=H * head_dim)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(_PAGED_CALL[program], v5e,
                 *_paged_args(program, kv_dtype, block, head_dim,
                              max_seq=block), interpret=False)


@pytest.mark.parametrize("case", _GRID_WALKED + _STEP_WALKED,
                         ids=_geometry_id)
def test_either_walk_lowers(v5e, case):
    """A pool the step cannot copy lowers with the walk in the grid (four
    grid dimensions, no copy of the step's own), any other with the walk in
    the step (three, and its DMAs)."""
    program, kv_dtype, block, head_dim, *heads = case
    (heads,) = heads or (H,)
    in_step = pa._copies_its_blocks(block, heads * head_dim)
    assert in_step == (case in _STEP_WALKED)
    assert pa.supports_paged_attention(
        head_dim=head_dim, block_size=block, kv_dtype=kv_dtype,
        interpret=False, program=program, n_embd=heads * head_dim)
    args = _paged_args(program, kv_dtype, block, head_dim, heads=heads)
    _compile(_PAGED_CALL[program], v5e, *args, interpret=False)
    kernel = str(jax.make_jaxpr(
        lambda *a: _PAGED_CALL[program](*a, interpret=False))(*args))
    assert ("dma_start" in kernel) == in_step


def test_verify_and_adapter_rules_match_the_compiler(v5e):
    """The satellite programs' side of the same rule: a head tile past
    the budget is refused by predicate and compiler alike; small ranks
    and an ``n_embd`` off the lanes are admitted and lower."""
    kw = dict(head_dim=DH, block_size=BLOCK, kv_dtype=jnp.bfloat16,
              interpret=False)
    assert pa.supports_paged_attention(program="verify", n_embd=100, **kw)
    _compile(pa._verify_tail_call, v5e, S((8, 100), jnp.float32),
             S((V_PAD, 100), jnp.float32), V, interpret=False)
    assert pa.supports_paged_attention(program="adapter", n_embd=D,
                                       adapter_rank=2, **kw)
    _adapter_delta(v5e, 2, jnp.float32)
    assert not pa.supports_paged_attention(program="verify", n_embd=4096,
                                           **kw)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(pa._verify_tail_call, v5e, S((8, 4096), jnp.float32),
                 S((V_PAD, 4096), jnp.float32), V, interpret=False)


# --------------------------------------------------------------------------
# The whole trusted step, for one described chip and for four
# --------------------------------------------------------------------------


@pytest.mark.slow  # ~55 s a case on one core (a whole step compiled)
@pytest.mark.parametrize("n_devices", [1, 4])
def test_trusted_step_lowers_for_described_chips(monkeypatch, n_devices):
    """The data-parallel trusted step on a described v5e: one device holds
    the detector's Mosaic moments kernel; four devices are a GSPMD
    program, which cannot hold one ("Mosaic kernels cannot be
    automatically partitioned") — the step must still lower there, on the
    XLA reductions, with its collectives.  The model is narrow but wide
    enough for a gradient leaf to reach the kernel's tile."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from trustworthy_dl_tpu import DistributedTrainer, TrainingConfig
    from trustworthy_dl_tpu.core.mesh import DATA_AXIS

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"cannot describe a v5e topology: {exc}")
    config = TrainingConfig(model_name="gpt2", batch_size=4, num_nodes=4,
                            parallelism="data", async_host_depth=0)
    trainer = DistributedTrainer(
        config, mesh=Mesh(np.array(jax.devices()[:n_devices]), (DATA_AXIS,)),
        model_overrides=dict(n_layer=1, n_embd=128, n_head=4,
                             vocab_size=512, n_positions=32, seq_len=32))
    trainer.initialize()
    batch = trainer._node_batch(jax.tree_util.tree_map(
        np.asarray, trainer.model.example_batch(4, jax.random.PRNGKey(0))))
    tpu_mesh = Mesh(np.array(topo.devices[:n_devices]), (DATA_AXIS,))

    def described(a):
        spec = (a.sharding.spec if isinstance(a.sharding, NamedSharding)
                else PartitionSpec())
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(tpu_mesh, spec))

    args = jax.tree_util.tree_map(
        described, (trainer.state, batch, trainer.attack_plan))
    # The dispatch predicates ask the backend; the trace is for the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = trainer._train_step.lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == (n_devices == 1)
    assert ("all-reduce" in text) == (n_devices == 4)
