"""The controls of zaya1_8b_l16.serve_reason128 (PR 30): the whole cell
through benchmark/run.py with ONE fault in the serving programs, each a
one-line change of `paddle_tpu/nn/decode_blocks.py` applied to the module
in memory (the file is not touched).  Every one must print `"correct":
false`; PERF.md section 6 has which of the check's three limits each
trips on the chip.  From the root of a checkout, on the chip:

    chiprun -- python3 scripts/zaya_faults.py <fault> \
        --workload zaya1_8b_l16.serve_reason128 --seed <n> \
        --seconds 10 --trace 0

`<fault>` is one of FAULTS' names (or `none`: the cell as it is).

`quick` in place of a fault runs them ALL in one process at a fraction of
the chip time: the configuration's model at the published widths, built
once; for `none` and then for every fault a new `PagedGenerationServer`
with the deployment's own options serves QUICK_REQUESTS requests of the
cell's traffic (their outputs cut to QUICK_NEW tokens), and the kind's own
`check_against_reference` and `verdict` judge what it served: the same
check, on an engine's own requests, without the timed window.

    chiprun -- python3 scripts/zaya_faults.py quick --seed <n> [--only a,b]
"""
import inspect
import runpy
import sys

for p in ("benchmark", "."):
    sys.path.insert(0, p)

# name -> [(the program's line, what the fault makes of it)]
FAULTS = {
    "no_rotary": [
        ("q = apply_rotary(q, pos, cc.rotary_dim, cc.theta).astype(dt)",
         "q = q.astype(dt)"),
        ("k = apply_rotary(k, pos, cc.rotary_dim, cc.theta).astype(dt)",
         "k = k.astype(dt)")],
    # query head h attends the K/V head of head Hq - 1 - h
    "wrong_kv_head": [
        ("        o = attend(q, kc, vc)\n        return o.reshape(",
         "        o = attend(q[:, ::-1], kc, vc)\n"
         "        return o[:, ::-1].reshape(")],
    # v_t = [W_v1 a_t | W_v2 a_t]: the second half is not the token before's
    "no_value_shift": [('back("v_prev", v2, 1)', "v2")],
    # a chunk starts from zero tails whatever its sequence left
    "tail_zeroed_at_chunk_start": [
        ('                         store[li, ctx["slot_row"]], 0)',
         '                         0 * store[li, ctx["slot_row"]], 0)')],
    # a prefill's last chunk leaves zero tails for the first decode step
    "tail_zeroed_before_decode": [
        ("leave=lambda z, tail: stream_tail(ctx, z, tail),",
         "leave=lambda z, tail: 0 * stream_tail(ctx, z, tail),")],
    # a decode step leaves the tails it found
    "tail_not_shifted": [
        ("leave=lambda z, tail: jnp.concatenate([tail[:, 1:], z[:, None]],",
         "leave=lambda z, tail: jnp.concatenate([tail[:, 1:], tail[:, -1:]],")],
    "router_state_dropped": [
        ("            if r_before is not None:\n                r = r + ",
         "            if False:\n                r = r + ")],
    # a row's position rounded to bf16's 8 bits (position 1,000 is 1,000,
    # 1,001 is 1,000, 3,001 is 3,008) before the rotary angle is formed,
    # which ops/rotary.py forms in float32 whatever the model's dtype
    # (`reduce_precision`: a cast pair would be optimised away)
    "rotary_bf16": [
        ("q = apply_rotary(q, pos, cc.rotary_dim, cc.theta).astype(dt)",
         "q = apply_rotary(q, jax.lax.reduce_precision(pos.astype(f32), 8, "
         "7), cc.rotary_dim, cc.theta).astype(dt)"),
        ("k = apply_rotary(k, pos, cc.rotary_dim, cc.theta).astype(dt)",
         "k = apply_rotary(k, jax.lax.reduce_precision(pos.astype(f32), 8, "
         "7), cc.rotary_dim, cc.theta).astype(dt)")],
    # the router's float32 matmuls in one bf16 pass (the TPU's default)
    "router_bf16": [("hi = jax.lax.Precision.HIGHEST",
                     "hi = jax.lax.Precision.DEFAULT")],
}


_PRISTINE = []


def apply(name):
    """Make `name` the one fault in the program (the module's source as
    it was imported, with that fault's lines changed)."""
    from paddle_tpu.nn import decode, decode_blocks

    if not _PRISTINE:
        _PRISTINE.append(inspect.getsource(decode_blocks))
    src = _PRISTINE[0]
    for old, new in FAULTS[name]:
        if src.count(old) != 1:
            raise SystemExit(f"fault {name!r}: the program's line is not "
                             f"there once: {old!r}")
        src = src.replace(old, new)
    decode._jitted_block_programs.cache_clear()
    exec(compile(src, decode_blocks.__file__, "exec"),
         decode_blocks.__dict__)


# 16 prompts of the cell's mix are ~5,600 tokens: three packed dispatches of
# 2,048, so some prompts are split between two of them
QUICK_REQUESTS, QUICK_NEW = 16, 48


def quick(seed, rehearse, only=None):
    """`none` and every fault (or those of `only`) through one process:
    {name: verdict}."""
    import gc
    import json
    import os

    import numpy as np

    import bench_data
    import paddle_tpu as paddle
    import run as bench
    from paddle_tpu.inference import PagedGenerationServer

    cell = "zaya1_8b_l16.serve_reason128"
    _b, _c, cfg, traffic = bench.load_cell(cell, rehearse)
    family = bench.load_plugin("families", cfg["family"])
    kind = bench.load_plugin("kinds", traffic["kind"])
    dep = cfg["deployment"]["serve"]
    paddle.seed(seed % (2 ** 31 - 1))
    model = family.served_model(cfg, dep["dtype"])
    print(f"[quick] routers balanced: {model.router_balance}", flush=True)
    params = dict(model.functional_state()[0])
    reference = family.reference(cfg)
    stream = bench_data.RequestStream(traffic, cfg["vocab_size"], seed)
    # no more requests than slots: each then holds its slot of the store
    # last, and its tails are still there when the server has stopped
    prompts = [next(stream)[0]
               for _ in range(min(QUICK_REQUESTS, dep["max_slots"]))]
    new = min(QUICK_NEW, dep["max_new_tokens"])
    engine = {k: v for k, v in dep.items() if k not in ("dtype", "sizing")}
    out = {}
    for name in ["none"] + sorted(only or FAULTS):
        if name != "none":
            apply(name)
        server = PagedGenerationServer(model, **engine)
        told = [[] for _ in prompts]
        slots = [0] * len(prompts)

        def note(i):
            def on_routing(position, picks, slot):
                told[i].append((position, picks))
                slots[i] = slot
            return on_routing

        server.start()
        try:
            futs = [server.submit(p, max_new_tokens=new, on_routing=note(i))
                    for i, p in enumerate(prompts)]
            seqs = [np.asarray(f.result(timeout=900)) for f in futs]
        finally:
            server.stop()
        sample = []
        for seq, prompt, mine, slot in zip(seqs, prompts, told, slots):
            layers, _n, k = mine[0][1].shape
            picks = np.full((layers, len(seq) - 1, k), -1, np.int32)
            for position, got in mine:
                picks[:, position:position + got.shape[1]] = got
            sample.append(kind.Served(seq, prompt, picks, {
                n: np.asarray(a[:, slot], np.float32)
                for n, a in server.cache.state.items()}))
        del server
        gc.collect()
        found = kind.check_against_reference(reference, params, sample,
                                             lambda *_a: None)
        wrong = kind.verdict(found)
        out[name] = {"correct": not wrong, "deficit": found["deficit"],
                     "gap": found["gap"], "outside": found["outside"],
                     "swapped": found["swapped"],
                     "positions": found["positions"],
                     "spread": found["spread"], "tails": found["tails"],
                     "tails_by_name": found["tails_by_name"]}
        print(f"[quick] {name}: {json.dumps(out[name])}", flush=True)
        for why in wrong:
            print(f"[quick] {name}: [wrong] {why}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/zaya_faults_quick.json", "w") as f:
        json.dump({"seed": seed, "faults": out}, f, indent=1)
    bad = [n for n, v in out.items() if v["correct"] != (n == "none")]
    print(f"[quick] every fault fails and the program passes: {not bad}"
          + (f" (not so: {bad})" if bad else ""), flush=True)
    return 0 if not bad else 1


if __name__ == "__main__":
    fault = sys.argv[1]
    if fault == "quick":
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--seed", type=int, default=1)
        ap.add_argument("--rehearse", action="store_true")
        ap.add_argument("--only", default="", help="faults, comma-separated")
        args = ap.parse_args(sys.argv[2:])
        sys.exit(quick(args.seed, args.rehearse,
                       [f for f in args.only.split(",") if f]))
    if fault != "none":
        apply(fault)
    print(f"[fault] {fault}", flush=True)
    sys.argv = ["benchmark/run.py"] + sys.argv[2:]
    runpy.run_path("benchmark/run.py", run_name="__main__")
