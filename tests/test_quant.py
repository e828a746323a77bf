"""int8 quantization tier (trustworthy_dl_tpu/quant + serve int8 KV +
weight-only int8 decode).

Fast tier, ``quant`` marker.  The parity tests jit the 2-layer/32-dim
tiny GPT-2 (seconds, shared via the module params fixture); everything
else is host math.  THE acceptance pins: greedy tokens through the
int8-KV engine equal the f32-KV engine's (which equal batch
``generate()``'s), the decode step still compiles exactly once per
engine, int8 halves the KV value bytes per slot, and slot reuse after a
quantized prefill cannot leak a stale scale."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.core.config import ServeConfig
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate, _decode_view
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.ops.fused_dequant_matmul import (
    _dq_matmul_pallas,
    dequant_matmul,
)
from trustworthy_dl_tpu.quant import int8 as q8
from trustworthy_dl_tpu.serve import (
    PagedBatchingScheduler,
    ServeRequest,
    ServingEngine,
    init_paged_pool,
    kv_bytes_per_token,
)

pytestmark = pytest.mark.quant

# vocab_size deliberately differs from tests/test_serve.py's 97: the
# prefill/decode jit caches are process-global (scheduler._PROGRAMS), so
# an identical config here would make test_serve's strict compile-once
# pin (`decode_cache_size() - before == 1`) see a cache HIT when both
# files run in one process.  A distinct logits shape keeps every
# compile-count pin honest in either file order.
CFG = gpt2.GPT2Config(vocab_size=101, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# Primitives: roundtrip error bounds, per input dtype
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_roundtrip_error_bound(dtype):
    """Symmetric int8 roundtrip error is bounded by half a step of the
    per-channel amax: |x - deq(q(x))| <= amax_channel / 254 (plus the
    input's own precision for bf16 sources)."""
    x = (jax.random.normal(jax.random.PRNGKey(1), (6, 33, 64))
         .astype(dtype))
    q, scale = q8.quantize_int8(x, axis=-1)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert scale.shape == (6, 33)
    back = q8.dequantize_int8(q, scale, axis=-1)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    bound = amax / (2 * q8.QMAX) * 1.001
    if dtype == jnp.bfloat16:
        bound = bound + amax * 2 ** -8  # source rounding
    err = jnp.max(jnp.abs(x.astype(jnp.float32) - back), axis=-1)
    assert bool(jnp.all(err <= bound)), float(jnp.max(err - bound))


def test_quantize_zero_channel_is_exact():
    """All-zero channels store scale 0 and dequantise to exact zeros —
    no divide-by-zero, no NaN (untouched cache rows rely on this)."""
    x = jnp.zeros((4, 16))
    q, scale = q8.quantize_int8(x, axis=-1)
    assert bool(jnp.all(scale == 0.0))
    back = q8.dequantize_int8(q, scale, axis=-1)
    assert bool(jnp.all(back == 0.0)) and bool(jnp.all(jnp.isfinite(back)))


def test_quantize_dense_stacked_blocks_layout():
    """Per-output-channel scales reduce the ``in`` axis and keep the
    model's stacked [L, in, out] block layout intact."""
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 32, 96))
    d = q8.quantize_dense({"w": w, "b": jnp.zeros((3, 96))})
    assert d["w_q"].shape == (3, 32, 96) and d["w_q"].dtype == jnp.int8
    assert d["scale"].shape == (3, 96)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 32))
    ref = x @ w[0]
    got = q8.qdense({"w_q": d["w_q"][0], "scale": d["scale"][0],
                     "b": jnp.zeros(96)}, x)
    # Weight-only int8 error: bounded by in_dim * per-element step.
    assert float(jnp.max(jnp.abs(ref - got))) < 0.05 * float(
        jnp.max(jnp.abs(ref))
    ) + 1e-3


def test_pallas_dequant_matmul_matches_jnp_in_interpret_mode():
    """The fused dequant-matmul tile (interpret mode — CPU) equals the
    jnp contraction it replaces; non-tiling shapes fall back cleanly."""
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 128))
    w = jax.random.normal(jax.random.PRNGKey(5), (128, 256))
    w_q, scale = q8.quantize_int8(w, axis=-2)
    ref = dequant_matmul(x, w_q, scale)            # jnp path off-TPU
    ker = _dq_matmul_pallas(x, w_q, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               rtol=1e-6, atol=1e-5)
    # Non-tiling N (not a lane multiple) must still answer via jnp.
    odd = dequant_matmul(x[:, :100], w_q[:100, :200][:, :100],
                         scale[:100])
    assert odd.shape == (8, 100)
    # Odd M must NOT gate out the fused tile — decode's M is MAX_SLOTS,
    # which HBM budgets set to non-sublane counts (e.g. 15); dispatch
    # pads the row dim to the f32 sublane and slices it back.
    from trustworthy_dl_tpu.ops.fused_dequant_matmul import (
        dequant_matmul_tiles,
    )
    assert dequant_matmul_tiles(15, 128, 256)
    x15 = jax.random.normal(jax.random.PRNGKey(6), (15, 128))
    pad = jnp.concatenate([x15, jnp.zeros((1, 128))], axis=0)
    ker15 = _dq_matmul_pallas(pad, w_q, scale, interpret=True)[:15]
    np.testing.assert_allclose(np.asarray(dequant_matmul(x15, w_q, scale)),
                               np.asarray(ker15), rtol=1e-6, atol=1e-5)


# --------------------------------------------------------------------------
# Serving: parity, compile-once, slot reuse, capacity math
# --------------------------------------------------------------------------


def _run_workload(engine, n=6, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(3, 12))
        new = int(rng.integers(1, 9))
        prompt = rng.integers(0, CFG.vocab_size, plen).tolist()
        reqs.append((prompt, new))
        assert engine.submit(ServeRequest(prompt=prompt,
                                          max_new_tokens=new)) == i
    return reqs, engine.run_until_idle()


def test_greedy_parity_int8_kv_vs_f32_through_engine(params):
    """THE parity acceptance: heterogeneous greedy requests through a
    3-slot int8-KV engine (slot reuse forced) emit the same tokens as
    the f32-KV engine AND batch generate; the quantized decode step
    compiles exactly once for the engine's lifetime (the compile-count
    pin of test_serve extended to the quantized path)."""
    eng_ref = ServingEngine(params, CFG, max_slots=3, max_seq=48)
    before = eng_ref.scheduler.decode_cache_size()
    reqs, res_ref = _run_workload(eng_ref)
    assert eng_ref.scheduler.decode_cache_size() - before == 1

    eng_q = ServingEngine(params, CFG, max_slots=3, max_seq=48,
                          kv_dtype="int8", weight_dtype="int8")
    assert eng_q.kv_fallback_reason is None
    assert eng_q.scheduler.kv.quantized
    before = eng_q.scheduler.decode_cache_size()
    reqs_q, res_q = _run_workload(eng_q)
    # ONE compiled decode program for the whole quantized run too.
    assert eng_q.scheduler.decode_cache_size() - before == 1

    assert reqs == reqs_q
    for rid, (prompt, new) in enumerate(reqs):
        ref = generate(params, CFG, jnp.asarray([prompt], jnp.int32), new,
                       temperature=0.0)
        ref_tokens = np.asarray(ref)[0, len(prompt):].tolist()
        assert res_ref[rid].tokens == ref_tokens, f"f32 request {rid}"
        assert res_q[rid].tokens == ref_tokens, f"int8 request {rid}"


def test_slot_reuse_after_quantized_prefill_overwrites_stale_scales(params):
    """A slot reused after a LONG quantized generation must not leak the
    previous occupant's scales: the second request's stream equals a
    fresh engine's, and the prefill overwrote the scale rows for every
    position the new request can ever attend to."""
    engine = ServingEngine(params, CFG, max_slots=1, max_seq=48,
                           kv_dtype="int8")
    tables = {}

    def note_table(rid, _token):
        tables.setdefault(rid, list(engine.scheduler.tables[0]))

    first = engine.submit(ServeRequest(prompt=[9, 8, 7, 6, 5, 4, 3, 2],
                                       max_new_tokens=8,
                                       on_token=note_table))
    second = engine.submit(ServeRequest(prompt=[1, 2, 3],
                                        max_new_tokens=4,
                                        on_token=note_table))
    results = engine.run_until_idle()
    assert results[first].tokens and results[second].tokens

    fresh = ServingEngine(params, CFG, max_slots=1, max_seq=48,
                          kv_dtype="int8")
    rid = fresh.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=4))
    assert fresh.run_until_idle()[rid].tokens == results[second].tokens
    # Direct scale hygiene: the second request reused the first one's
    # freed block (prompt+new = 7 positions fit one block of 16), and
    # its prefill re-wrote the scales from position 0.
    assert tables[second][0] == tables[first][0]
    ks = np.asarray(engine.scheduler.kv.k_scale)[:, tables[second][0]]
    assert np.all(ks[:, :, :3] > 0.0)   # [L, H, B]: prompt rows re-quantized


def test_int8_halves_kv_value_bytes_and_slot_capacity(params):
    """int8 KV value arrays are exactly half the bf16 pool's bytes (a
    quarter of f32); at GPT-2 head dims the per-token total (values +
    scales) admits >= 1.5x tokens at equal HBM."""
    # 4 sequences of 48 positions in blocks of 16: 12 blocks + trash.
    bf16 = init_paged_pool(CFG, 12, 16, kv_dtype=jnp.bfloat16)
    q = init_paged_pool(CFG, 12, 16, kv_dtype=jnp.int8)
    assert q.k.nbytes * 2 == bf16.k.nbytes
    assert q.v.nbytes * 2 == bf16.v.nbytes
    assert q.k_scale.shape == (CFG.n_layer, 13, 16, CFG.n_head)
    assert q.bytes_per_block == 16 * kv_bytes_per_token(CFG, jnp.int8)
    # Capacity math at real serving dims (no allocation): gpt2 Dh=64.
    full = gpt2.GPT2Config.from_name("gpt2")
    ratio = (kv_bytes_per_token(full, jnp.bfloat16)
             / kv_bytes_per_token(full, jnp.int8))
    assert ratio >= 1.5, ratio


def test_parity_failure_falls_back_to_model_dtype(params, monkeypatch):
    """The safety latch: a failed parity probe silently (but loudly
    logged) swaps the pool back to the model dtype — serving proceeds,
    nothing quantized, reason recorded — AND the block pool shrinks to
    what the int8 byte budget buys at model-dtype cost, so an engine
    sized to fill HBM at int8 bytes/token cannot over-allocate on
    fallback."""
    monkeypatch.setattr("trustworthy_dl_tpu.quant.int8.kv_parity_probe",
                        lambda *a, **k: False)
    # The BLOCK count shrinks to what the int8
    # byte budget buys at model-dtype cost (6 int8 blocks * 192 B/token
    # // 512 B/token = 2, clamped to the one-full-sequence floor of 3).
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                           kv_dtype="int8")
    assert engine.kv_fallback_reason == "kv_parity_probe_failed"
    assert engine.kv_dtype == "model"
    assert not engine.scheduler.kv.quantized
    assert engine.scheduler.kv.num_blocks == 3
    rid = engine.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=2))
    assert engine.run_until_idle()[rid].status == "completed"


# --------------------------------------------------------------------------
# Contracts: loud dtype validation + obs gauges
# --------------------------------------------------------------------------


def test_unknown_dtypes_fail_loudly_at_construction(params):
    """Unknown kv_dtype/weight_dtype strings raise at ServeConfig /
    engine / scheduler construction — never at trace time."""
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="int4")
    with pytest.raises(ValueError, match="weight_dtype"):
        ServeConfig(weight_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(params, CFG, kv_dtype="e4m3")
    with pytest.raises(ValueError, match="weight_dtype"):
        PagedBatchingScheduler(params, CFG, 2, 32, weight_dtype="nf4")
    # The valid surface stays constructible.
    ServeConfig(kv_dtype="int8", weight_dtype="int8")
    ServeConfig()  # defaults


def test_kv_pool_gauges_and_quant_error_histogram(params):
    """The serve registry carries the KV-pool capacity surface
    (tddl_serve_kv_bytes, tddl_serve_slots_total{dtype=}) and the
    weight-roundtrip quantization-error histogram."""
    registry = MetricsRegistry()
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           kv_dtype="int8", weight_dtype="int8",
                           kv_parity_check=False, registry=registry)
    assert registry.get("tddl_serve_kv_bytes").value() == float(
        engine.scheduler.kv.pool_bytes
    )
    assert registry.get("tddl_serve_slots_total").value(dtype="int8") == 2.0
    # One roundtrip-error observation per decode weight matrix kind.
    assert registry.get("tddl_serve_quant_error").value()["count"] == 4
    # The same metrics ride any snapshot an ObsSession would publish.
    snap = registry.snapshot()["metrics"]
    assert "tddl_serve_kv_bytes" in snap
    assert snap["tddl_serve_slots_total"]["series"][0]["labels"] == {
        "dtype": "int8"
    }
