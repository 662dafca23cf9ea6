"""The controls of kanana2_30b_l6.serve_longdoc64: the cell's check
with ONE fault in the serving programs, each a changed line of
`paddle_tpu/nn/decode_blocks.py` applied to the module in memory (the file
is not touched).  Every one must come out not correct; PERF.md section 6
has which of the check's three limits each trips on the chip.  `pool_fp8`
is the precision control: the program as it is but for a latent pool that
keeps its rows at float8 (e4m3)'s precision, the nearest below the bf16
the configuration states.  It runs them ALL in one process, at a fraction
of the chip time of a whole cell a fault: the configuration's model at
the published widths, built once; for `none` and then for every fault a
new `PagedGenerationServer` with the deployment's own options serves
QUICK_REQUESTS prompts of the cell's traffic at once (each cut to whole
prefill chunks, so that one packed bucket and the decode step are all a
fault compiles; their outputs cut to QUICK_NEW tokens), and the kind's own
`sample_of`, `check_against_reference` and `verdict` judge the
QUICK_CHECKED shortest: the same check, on an engine's own requests,
without the timed window.
From the root of a checkout, on a machine with the chip:

    python3 scripts/kanana2_faults.py quick --seed <n> [--only a,b]

prints each reading as a JSON line, then whether every fault fails and
the program passes (its exit code: 0 if so).
"""
import inspect
import sys

for p in ("benchmark", "."):
    sys.path.insert(0, p)

BLOCKS = "paddle_tpu.nn.decode_blocks"

# name -> [(module, the program's line, what the fault makes of it)]
FAULTS = {
    # neither q_pe nor k_pe turns by its position
    "no_rotary": [
        (BLOCKS, "        if desc.mla_theta is not None:    # one key head",
         "        if False:    # one key head")],
    # q_pe turns, the shared k_pe written into the pool does not
    "rotary_q_only": [
        (BLOCKS, "            k_pe = k_pe[:, 0]",
         "            k_pe = kva[:, LORA:]")],
    # channels i and i + 32 paired, where the layout pairs 2i and 2i + 1
    "halves_pairing": [
        (BLOCKS, "                                       desc.mla_interleaved)",
         "                                       False)")],
    # the two shared experts' SwiGLU cut to one expert's width
    "shared_one_expert_wide": [
        (BLOCKS,
         '        return y + swiglu(p, m + "shared.", x), counts, picks, carry',
         '        f = p[m + "experts.gate"].shape[-1]\n'
         '        return y + (jax.nn.silu(x @ p[m + "shared.gate_proj.weight"]'
         '[:, :f]) * (x @ p[m + "shared.up_proj.weight"][:, :f])) '
         '@ p[m + "shared.down_proj.weight"][:f], counts, picks, carry')],
    # the routed sum not scaled by routed_scaling_factor
    "no_routed_scaling": [
        (BLOCKS, "            top_k=desc.top_k, scaling=desc.scaling,\n"
                 "            renormalize=desc.renormalize)\n"
                 "        return y + swiglu",
         "            top_k=desc.top_k, scaling=1.0,\n"
         "            renormalize=desc.renormalize)\n"
         "        return y + swiglu")],
    # the latent c written without its RMSNorm
    "no_kv_norm": [
        (BLOCKS, '[rms(kva[:, :LORA], p[pre + "kv_norm.weight"]), k_pe,',
         "[kva[:, :LORA], k_pe,")],
    # the precision control: a pool that keeps its rows at float8 (e4m3)'s
    # 3 mantissa bits (`reduce_precision`: a cast pair would be optimised
    # away)
    "pool_fp8": [
        (BLOCKS, "        kc = kc.at[li, blk, off].set(lat.astype(kc.dtype))",
         "        kc = kc.at[li, blk, off].set(jax.lax.reduce_precision("
         "lat, 4, 3).astype(kc.dtype))")],
}


_PRISTINE = {}


def apply(name):
    """Make `name` the one fault in the program (`none`: none): the
    module's source as it was imported, with that fault's lines changed,
    and the programs built from it forgotten."""
    import importlib

    from paddle_tpu.nn import decode

    mods = {m: importlib.import_module(m) for m in (BLOCKS,)}
    for m, mod in mods.items():
        _PRISTINE.setdefault(m, inspect.getsource(mod))
    src = dict(_PRISTINE)
    for m, old, new in FAULTS.get(name, []):
        if src[m].count(old) != 1:
            raise SystemExit(f"fault {name!r}: the program's line is not "
                             f"there once in {m}: {old!r}")
        src[m] = src[m].replace(old, new)
    decode._jitted_block_programs.cache_clear()
    for m, mod in mods.items():
        exec(compile(src[m], mod.__file__, "exec"), mod.__dict__)


# 8 prompts of the cell's mix cut to whole chunks of 2,048 (one packed
# bucket), 48 new tokens each; the 4 shortest keep the reference short
QUICK_REQUESTS, QUICK_NEW, QUICK_CHECKED = 8, 48, 4


def quick(seed, rehearse, only=None):
    """`none` and every fault (or those of `only`) through one process:
    {name: verdict}."""
    import gc
    import json
    import time

    import numpy as np

    import bench_data
    import paddle_tpu as paddle
    import run as bench
    from paddle_tpu.inference import PagedGenerationServer

    cell = "kanana2_30b_l6.serve_longdoc64"
    bench.configure_jax(cell, rehearse)     # the cell's own compile cache
    _b, _c, cfg, traffic = bench.load_cell(cell, rehearse)
    family = bench.load_plugin("families", cfg["family"])
    kind = bench.load_plugin("kinds", traffic["kind"])
    dep = cfg["deployment"]["serve"]
    paddle.seed(seed % (2 ** 31 - 1))
    model = family.served_model(cfg, dep["dtype"])
    params = dict(model.functional_state()[0])
    reference = family.reference(cfg)
    stream = bench_data.RequestStream(traffic, cfg["vocab_size"], seed)
    chunk = dep["prefill_chunk_tokens"]
    prompts = []
    for _ in range(QUICK_REQUESTS):
        p = next(stream)[0]
        prompts.append(p[:max(chunk, len(p) // chunk * chunk)]
                       if len(p) >= chunk else p)
    new = min(QUICK_NEW, dep["max_new_tokens"])
    checked = sorted(range(len(prompts)),
                     key=lambda i: len(prompts[i]))[:QUICK_CHECKED]
    engine = {k: v for k, v in dep.items() if k not in ("dtype", "sizing")}
    out = {}
    for name in ["none"] + sorted(only or FAULTS):
        apply(name)
        server = PagedGenerationServer(model, **engine)
        recorded = kind.Recorded(server)
        requests = [kind.serve.Request(i, p, new, 0.0)
                    for i, p in enumerate(prompts)]
        server.start()
        t0 = time.perf_counter()
        try:
            futs = [recorded.submit(r.prompt, max_new_tokens=new,
                                    on_token=r.on_token) for r in requests]
            for r, f in zip(requests, futs):
                r.seq = np.asarray(f.result(timeout=900))
                r.t_done = time.perf_counter()
        finally:
            server.stop()
        served_s = time.perf_counter() - t0
        sample = kind.sample_of(requests, recorded,
                                [requests[i] for i in checked],
                                bench_data.rng(seed, 4), QUICK_CHECKED)
        del server, recorded
        gc.collect()
        found = kind.check_against_reference(reference, params, sample,
                                             lambda *_a: None)
        wrong = kind.verdict(found)
        out[name] = {"correct": not wrong, "served_s": round(served_s, 1),
                     **{k: found[k] for k in (
                         "deficit", "exact", "tokens", "gap", "outside",
                         "swapped", "positions", "latent", "latent_by_part",
                         "rows")}}
        print(f"[quick] {name}: {json.dumps(out[name])}", flush=True)
        for why in wrong:
            print(f"[quick] {name}: [wrong] {why}", flush=True)
    apply("none")
    bad = [k for k, v in out.items() if v["correct"] != (k == "none")]
    print(f"[quick] every fault fails and the program passes: {not bad}"
          + (f" (not so: {bad})" if bad else ""), flush=True)
    return 0 if not bad else 1


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["quick"])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default="", help="faults, comma-separated")
    args = ap.parse_args()
    sys.exit(quick(args.seed, args.rehearse,
                   [f for f in args.only.split(",") if f]))
