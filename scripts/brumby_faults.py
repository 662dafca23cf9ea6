"""The controls of brumby_14b_l8.serve_decode16 (PR 32): the cell's check
with ONE fault in the serving programs, each a changed line (or the same
change in the two forms of one thing) of `paddle_tpu/nn/decode_blocks.py`,
`paddle_tpu/ops/power_retention.py` or
`paddle_tpu/ops/pallas/power_decode.py`, applied to the modules in memory
(the files are not touched).  Every one must come out not correct;
PERF.md section 6 has which of the check's two limits each trips on the
chip.  `state_bf16` is the precision control: the program as it is but
for a store that keeps its state at bf16's precision.  From the root of a
checkout, on the chip:

    chiprun -- python3 scripts/brumby_faults.py <fault> \
        --workload brumby_14b_l8.serve_decode16 --seed <n> \
        --seconds 10 --trace 0

runs the whole cell through benchmark/run.py with `<fault>` (one of
FAULTS' names, or `none`: the cell as it is).

`quick` in place of a fault runs them ALL in one process at a fraction of
the chip time: the configuration's model at the published widths, built
once; for `none` and then for every fault a new `PagedGenerationServer`
with the deployment's own options serves two generations of QUICK_REQUESTS
requests of the cell's traffic (their outputs cut to QUICK_NEW tokens; the
second generation takes the slots the first held), and the kind's own
`check_against_reference` and `verdict` judge the QUICK_CHECKED shortest
of the second generation: the same check, on an engine's own requests,
without the timed window.

    chiprun -- python3 scripts/brumby_faults.py quick --seed <n> [--only a,b]
"""
import inspect
import runpy
import sys

for p in ("benchmark", "."):
    sys.path.insert(0, p)

BLOCKS = "paddle_tpu.nn.decode_blocks"
OPS = "paddle_tpu.ops.power_retention"
KERNEL = "paddle_tpu.ops.pallas.power_decode"

# name -> [(module, the program's line, what the fault makes of it)]
FAULTS = {
    # every gate open: the state never decays
    "no_gate": [
        (BLOCKS, "jnp.where(valid[:, None], gamma, 0.0))",
         "jnp.where(valid[:, None], 0.0 * gamma, 0.0))")],
    "no_rotary": [
        (BLOCKS,
         "            return apply_rotary(rms(x, p[pre + norm]).astype(f32), "
         "pos,\n                                pw.head_dim, pw.theta)",
         "            return rms(x, p[pre + norm]).astype(f32)")],
    "no_qk_norm": [
        (BLOCKS, "apply_rotary(rms(x, p[pre + norm]).astype(f32), pos,",
         "apply_rotary(x.astype(f32), pos,")],
    # query head h goes with the K/V head of head Hq - 1 - h
    "wrong_kv_head": [
        (BLOCKS, 'q = heads("q_proj.weight", pw.heads, "q_norm.weight")',
         'q = heads("q_proj.weight", pw.heads, "q_norm.weight")[:, ::-1]')],
    # a chunk's own weights of degree 1 (the state's stay of degree 2)
    "degree_1": [
        (OPS, 'w = jnp.einsum("trd,sd->tsr", qs, ks) ** 2 * decay',
         'w = jnp.einsum("trd,sd->tsr", qs, ks) ** 1 * decay')],
    # the outputs are the weighted sums, not divided by the weights' sum
    "no_normaliser": [
        (OPS, "    return num / (den[..., None] + eps), s_c, z_c",
         "    return num + 0 * den[..., None], s_c, z_c"),
        (OPS, "    return num / (den.reshape(b, hq, 1) + eps), store_s, "
              "store_z",
         "    return num + 0 * den.reshape(b, hq, 1), store_s, store_z")],
    # a sequence goes on from whatever its slot's last holder left
    "state_not_zeroed_on_slot_reuse": [
        (OPS, "qc, kc, vc, gc, jnp.where(new, 0.0, "
              "st_s[layer, slot, head]),",
         "qc, kc, vc, gc, st_s[layer, slot, head],"),
        (OPS, "jnp.where(new, 0.0, st_z[layer, slot, head]), tile=tile, "
              "eps=eps,",
         "st_z[layer, slot, head], tile=tile, eps=eps,")],
    # the products of two different tiles counted once, not twice
    "offdiagonal_weight_1": [
        (OPS, "np.where(pairs[:, 0] == pairs[:, 1], 1.0, np.sqrt(2.0))",
         "np.where(pairs[:, 0] == pairs[:, 1], 1.0, 1.0)"),
        (KERNEL, "w = 1.0 if a == b else math.sqrt(2.0)",
         "w = 1.0")],
    # the precision control: a store that keeps S and z at bf16's 8 bits,
    # rounded at every write of the decode kernel and of the prefill form
    # (`reduce_precision`: a cast pair would be optimised away)
    "state_bf16": [
        (KERNEL, "            so_ref[rows, :] = slab",
         "            so_ref[rows, :] = slab.astype(jnp.bfloat16)"
         ".astype(jnp.float32)"),
        (OPS, "        store_z = store_z.at[layer, slots].set(z)",
         "        store_z = store_z.at[layer, slots].set("
         "jax.lax.reduce_precision(z, 8, 7))"),
        (OPS, "        return (st_s.at[layer, slot, head].set(s_c),\n"
              "                st_z.at[layer, slot, head].set(z_c)), o",
         "        return (st_s.at[layer, slot, head].set("
         "jax.lax.reduce_precision(s_c, 8, 7)),\n"
         "                st_z.at[layer, slot, head].set("
         "jax.lax.reduce_precision(z_c, 8, 7))), o")],
}


_PRISTINE = {}


def apply(name):
    """Make `name` the one fault in the program (`none`: none): every
    module's source as it was imported, with that fault's lines changed,
    and the programs built from them forgotten."""
    import importlib

    from paddle_tpu.nn import decode

    mods = {m: importlib.import_module(m) for m in (KERNEL, OPS, BLOCKS)}
    for m, mod in mods.items():
        _PRISTINE.setdefault(m, inspect.getsource(mod))
    src = dict(_PRISTINE)
    for m, old, new in FAULTS.get(name, []):
        if src[m].count(old) != 1:
            raise SystemExit(f"fault {name!r}: the program's line is not "
                             f"there once in {m}: {old!r}")
        src[m] = src[m].replace(old, new)
    decode._jitted_block_programs.cache_clear()
    for m, mod in mods.items():     # the kernel first: the ops import it
        exec(compile(src[m], mod.__file__, "exec"), mod.__dict__)


# two generations of 16 prompts of the cell's mix are ~100,000 tokens: two
# hundred dispatches of 512, every prompt over 512 split between several;
# the 4 shortest last holders of a slot keep the reference short
QUICK_REQUESTS, QUICK_NEW, QUICK_CHECKED = 16, 48, 4


def quick(seed, rehearse, only=None):
    """`none` and every fault (or those of `only`) through one process:
    {name: verdict}."""
    import gc
    import json
    import os
    import time

    import numpy as np

    import bench_data
    import paddle_tpu as paddle
    import run as bench
    from paddle_tpu.inference import PagedGenerationServer

    cell = "brumby_14b_l8.serve_decode16"
    _b, _c, cfg, traffic = bench.load_cell(cell, rehearse)
    family = bench.load_plugin("families", cfg["family"])
    kind = bench.load_plugin("kinds", traffic["kind"])
    dep = cfg["deployment"]["serve"]
    paddle.seed(seed % (2 ** 31 - 1))
    model = family.served_model(cfg, dep["dtype"])
    params = dict(model.functional_state()[0])
    reference = family.reference(cfg)
    stream = bench_data.RequestStream(traffic, cfg["vocab_size"], seed)
    # the second generation takes the slots the first held (a slot goes to
    # a sequence with its first prefill chunk and comes back with its last
    # token, so it may change hands inside a generation too)
    n = min(QUICK_REQUESTS, dep["max_slots"])
    prompts = [next(stream)[0] for _ in range(2 * n)]
    new = min(QUICK_NEW, dep["max_new_tokens"])
    engine = {k: v for k, v in dep.items() if k not in ("dtype", "sizing")}
    out = {}
    for name in ["none"] + sorted(only or FAULTS):
        apply(name)
        server = PagedGenerationServer(model, **engine)
        slots, done = [0] * len(prompts), [0.0] * len(prompts)

        def note(i):
            def on_routing(_position, _picks, slot):
                slots[i] = slot
            return on_routing

        def stamp(i):
            def on_done(_future):
                done[i] = time.perf_counter()
            return on_done

        server.start()
        try:
            seqs = []
            for g in (range(n), range(n, 2 * n)):
                futs = []
                for i in g:
                    futs.append(server.submit(
                        prompts[i], max_new_tokens=new, on_routing=note(i)))
                    futs[-1].add_done_callback(stamp(i))
                seqs += [np.asarray(f.result(timeout=900)) for f in futs]
        finally:
            server.stop()
        # only a request that held its slot LAST still has its state there
        last = {}
        for i in range(2 * n):
            if slots[i] not in last or done[i] > done[last[slots[i]]]:
                last[slots[i]] = i
        checked = sorted((i for i in last.values() if i >= n),
                         key=lambda i: len(prompts[i]))[:QUICK_CHECKED]
        sample = [kind.Served(seqs[i], prompts[i], family.unpack_state(
            server.cache.state, slots[i], cfg)) for i in checked]
        reused = sorted(slots[i] for i in checked)
        del server
        gc.collect()
        found = kind.check_against_reference(reference, params, sample,
                                             lambda *_a: None)
        wrong = kind.verdict(found)
        out[name] = {"correct": not wrong, "deficit": found["deficit"],
                     "exact": found["exact"], "tokens": found["tokens"],
                     "state": found["state"],
                     "state_by_name": found["state_by_name"],
                     "fed": found["fed"], "slots": reused}
        print(f"[quick] {name}: {json.dumps(out[name])}", flush=True)
        for why in wrong:
            print(f"[quick] {name}: [wrong] {why}", flush=True)
    apply("none")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/brumby_faults_quick.json", "w") as f:
        json.dump({"seed": seed, "faults": out}, f, indent=1)
    bad = [k for k, v in out.items() if v["correct"] != (k == "none")]
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
    apply(fault)
    print(f"[fault] {fault}", flush=True)
    sys.argv = ["benchmark/run.py"] + sys.argv[2:]
    runpy.run_path("benchmark/run.py", run_name="__main__")
