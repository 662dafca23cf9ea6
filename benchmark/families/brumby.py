"""The brumby family: what a serving cell needs from the program to serve a
Brumby configuration through the entry points a user calls
(`models.brumby.Brumby`, `inference.PagedGenerationServer`), and the plain
reference it is checked against.  Keys of the configuration file are the
published config.json's; `layers` is what this chip holds (`reduced`),
`power_tile` and `power_chunk` what the config is silent on (`assumed`)."""
from __future__ import annotations

import sys

# imported before anything is built: a checkout that cannot serve this
# family fails here, at once, as run.py's own failures do (one line on
# standard error, exit code 2, no result line)
try:
    from paddle_tpu.models.brumby import Brumby, BrumbyConfig
except ImportError as e:
    print(f"benchmark: FAILED: this checkout cannot serve the brumby "
          f"family: {e}", file=sys.stderr, flush=True)
    raise SystemExit(2) from None


def program_config(cfg):
    return BrumbyConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        attention_bias=cfg["attention_bias"],
        max_position_embeddings=cfg["max_position_embeddings"],
        power_tile=cfg["power_tile"], power_chunk=cfg["power_chunk"],
        held_layers=cfg["layers"])


def serve_kernels(cfg):
    """Pallas kernels a program holds, by program and kernel name: every
    layer's decode step runs one `power_decode`; the chunked prefill form,
    rotary, the norms, the SwiGLU and the head are XLA."""
    return {"decode_step": {"power_decode": cfg["layers"]},
            "packed_prefill": {}}


def shape(cfg):
    """The sizes flops_brumby.py and the `.serve` metric readers need,
    under their names."""
    from paddle_tpu.ops.power_retention import state_dim

    c = program_config(cfg)
    return {
        "layers": c.held_layers, "hidden": c.hidden_size,
        "vocab": c.vocab_size, "heads": c.num_attention_heads,
        "kv_heads": c.num_key_value_heads, "head_dim": c.head_dim,
        "power_layers": c.held_layers,
        "power_state_dim": state_dim(c.head_dim, c.power_tile),
    }


def served_model(cfg, dtype):
    """The model with its weights from `paddle.seed`."""
    model = Brumby(program_config(cfg), dtype=dtype)
    model.eval()
    return model


def serving_path(cfg):
    """Which form the decode-side retention takes on this backend
    (`ops.power_retention.power_recurrent_step`: the platform alone
    chooses, nothing falls back)."""
    from paddle_tpu.ops.attention import _on_tpu

    return "pallas" if _on_tpu() else "xla"


def unpack_state(store, slot, cfg):
    """The first layer's state of `slot` in a cache's store, as the
    reference writes it: ({"S": [Hkv, d, d, d], "z": [Hkv, d, d]})."""
    from paddle_tpu.ops.power_retention import unpack_state as unpack

    s, z = unpack(store["P"][0, slot], store["Z"][0, slot],
                  cfg["power_tile"])
    return {"S": s, "z": z}


def reference(cfg):
    """(arch, hidden(params, ids, **kw) -> (x, found), head(params,
    rows) -> logits, query block) of benchmark/reference/brumby.py for
    this cut."""
    from reference import brumby as ref

    a = ref.arch(cfg)
    return (a, lambda params, ids, **kw: ref.hidden(params, ids, a, **kw),
            lambda params, rows: ref.head(params, rows, a),
            ref.ASSUMED["query_block"])
