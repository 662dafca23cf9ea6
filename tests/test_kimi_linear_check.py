"""The comparison that decides `correct` in the stateful serving cell
(`benchmark/kinds/serve_stateful.py`), run on the CPU through the harness's
own functions on a bf16 model with the published KDA head size (128): the
engine as it is passes, and the lower-precision control -- the same engine
with its recurrent-state store rounded to bf16 after every write -- fails,
by the state limit and by nothing else.  The chip's readings of both are in
PERF.md section 6 (PR 26); `scripts/kimi_control_bf16_state.py` runs the
whole cell under `bf16_state_store()`."""
import contextlib
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

from benchmark_harness import bench_paths  # noqa: F401 — sys.path
from reference import kimi_linear as ref
from run import load_plugin

kind = load_plugin("kinds", "serve_stateful")


@contextlib.contextmanager
def bf16_state_store():
    """The control: every state the KDA ops leave in the store is rounded
    to bf16 (a layer of the store after the decode step's kernel has
    written its rows, a prefill's chunk-end states before they are
    stored), as a store of that precision would hold them.  Programs
    built before or inside do not outlive the block."""
    from paddle_tpu.nn import decode, decode_blocks
    from paddle_tpu.ops import kda

    def rounded(x):
        return jax.lax.reduce_precision(x, 8, 7)

    step0, prefill0 = kda.kda_recurrent_step, kda.kda_chunked_prefill

    def step(store, layer, slots, *rest):
        o, store = step0(store, layer, slots, *rest)
        return o, store.at[layer].set(rounded(store[layer]))

    def prefill(*args, **kw):
        o, s_out = prefill0(*args, **kw)
        return o, rounded(s_out)

    caches = (decode._jitted_block_programs,
              decode_blocks.build_block_programs, decode_blocks._block_fns)
    for c in caches:
        c.cache_clear()
    kda.kda_recurrent_step, kda.kda_chunked_prefill = step, prefill
    try:
        yield
    finally:
        kda.kda_recurrent_step, kda.kda_chunked_prefill = step0, prefill0
        for c in caches:
            c.cache_clear()


class _Request:
    """What `serve.Request` is to the kind: the fields `sample_of` reads."""

    def __init__(self, prompt, new):
        self.prompt, self.new = prompt, new
        self.seq = self.error = self.t_done = None
        self.t_due = time.perf_counter()

    def on_token(self, _token, _reason):
        pass


CFG = dict(
    vocab_size=1024, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=64, num_hidden_layers=3, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    num_experts=16, num_experts_per_token=4, kda_layers=(1, 3),
    full_attn_layers=(2,), kda_num_heads=1, kda_head_dim=128,
    kda_gate_rank=16, kda_chunk=64, model_max_length=4096,
    held_experts=(0, 8))


def readings():
    """Serve two requests on a fresh bf16 model through the kind's own
    recorder, sample and check: `check_against_reference`'s findings."""
    paddle.seed(11)
    cfg = KimiLinearConfig(**CFG)
    model = KimiLinear(cfg, dtype="bfloat16")
    model.eval()
    server = PagedGenerationServer(
        model, max_slots=2, block_size=64, num_blocks=40,
        max_prompt_len=1024, max_new_tokens=384, prefill_chunk_tokens=256)
    client = kind.Recorded(server)
    g = np.random.default_rng(5)
    requests = [_Request(g.integers(1, cfg.vocab_size, n).astype(np.int32),
                         new) for n, new in ((300, 200), (700, 384))]
    server.start()
    try:
        futs = [client.submit(r.prompt, max_new_tokens=r.new,
                              on_token=r.on_token) for r in requests]
        for r, f in zip(requests, futs):
            r.seq = np.asarray(f.result(timeout=600))
            r.t_done = time.perf_counter()
    finally:
        server.stop()
    sample = kind.sample_of(requests, client.record, requests,
                            server.cache.state["S"],
                            np.random.default_rng(0), 2)
    assert len(sample) == 2
    a = {"hidden": cfg.hidden_size, "eps": cfg.rms_norm_eps,
         "kinds": tuple(m for m, _f in cfg.layer_kinds()),
         "dense_layers": cfg.first_k_dense_replace,
         "heads": cfg.num_attention_heads, "kda_heads": cfg.kda_num_heads,
         "kda_dim": cfg.kda_head_dim, "conv": cfg.short_conv_kernel_size,
         "nope": cfg.qk_nope_head_dim, "pe": cfg.qk_rope_head_dim,
         "v_dim": cfg.v_head_dim, "lora": cfg.kv_lora_rank,
         "top_k": cfg.num_experts_per_token,
         "renormalize": cfg.moe_renormalize,
         "scaling": cfg.routed_scaling_factor, "held": cfg.held_experts}
    reference = (a, lambda p, ids, **kw: ref.hidden(p, ids, a, **kw),
                 lambda p, rows: ref.head(p, rows, a))
    params = dict(model.functional_state()[0])
    return kind.check_against_reference(reference, params, sample,
                                        lambda _line: None)


@pytest.fixture(scope="module")
def sound():
    return readings()


@pytest.fixture(scope="module")
def control():
    with bf16_state_store():
        return readings()


def test_the_engine_as_it_is_passes_every_limit(sound):
    assert kind.verdict(sound) == [], sound
    assert sound["outside"] == 0 and sound["positions"] == 500 + 1084 - 2
    # room under each limit, as on the chip
    assert sound["state"] <= kind.STATE_LIMIT / 1.4
    assert sound["gap"] <= kind.NEAR_TIE / 1.4
    assert sound["deficit"] <= kind.LOGIT_MARGIN / 2


def test_a_bf16_state_store_fails_by_the_state_limit_alone(sound, control):
    wrong = kind.verdict(control)
    assert len(wrong) == 1 and "state" in wrong[0], (wrong, control)
    assert control["state"] >= 1.4 * kind.STATE_LIMIT
    assert control["state"] >= 2 * sound["state"]
    # what the other two limits read on it: tokens and routing still pass
    assert control["deficit"] <= kind.LOGIT_MARGIN
    assert control["gap"] <= kind.NEAR_TIE and control["outside"] == 0
