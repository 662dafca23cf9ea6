"""Endurance + failure drill (VERDICT r4 next #6): sustained GPT-2-small
training on the real chip with the full production stack — DataLoader
workers, watchdog armed, periodic sharded checkpoints — then a SIGKILL
mid-run and a resume from the checkpoint, with loss-curve continuity
checked across the kill.

    python scripts/endurance_drill.py --orchestrate \
        --dir /tmp/endurance --phase1-s 480 --phase2-s 360

Phase "run": trains until killed by its own SIGKILL timer (the
orchestrator expects rc=-9). Phase "resume": loads the newest sharded
checkpoint, continues, and the orchestrator then verifies: (a) the
resume restarted at the checkpointed step, (b) the first resumed loss
is within tolerance of the pre-kill trend, (c) the loss decreased over
the whole drill, (d) zero watchdog trips. Every step/loss lands in
loss_log.jsonl (append + flush: kill-safe).

The workload memorizes a FIXED 512-sequence corpus so the loss curve
is smooth and decreasing — continuity across the kill is meaningful,
unlike random-label noise.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# the DataLoader spawns its workers once this process holds the chip
# (io/worker.py: a TPU client's threads do not survive fork) — the
# dataset below is module-level picklable for exactly this

TINY = os.environ.get("PADDLE_TPU_DRILL_TINY") == "1"  # CPU smoke mode
INNER = 10          # steps per dispatch
# chip: ~1.5GB of f32 train state per save — space the checkpoints
# (200 steps ~= 40s of training between saves)
CKPT_EVERY = 2 if TINY else 20   # dispatches between ckpts
BATCH, SEQ = (4, 64) if TINY else (16, 1024)
CORPUS = 32 if TINY else 512     # fixed sequences to memorize


class Corpus:
    """Fixed seeded corpus; module-level so spawn-started workers can
    unpickle it (each worker regenerates the same array from the seed)."""

    def __init__(self, vocab):
        self.vocab = vocab
        self._data = None

    def _corpus(self):
        if self._data is None:
            rng = np.random.RandomState(7)
            self._data = rng.randint(0, self.vocab,
                                     (CORPUS, SEQ)).astype(np.int32)
        return self._data

    def __getstate__(self):
        return {"vocab": self.vocab, "_data": None}  # regen in worker

    def __len__(self):
        return CORPUS

    def __getitem__(self, i):
        return self._corpus()[i]


def _build(args):
    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401
    import paddle_tpu.io as pio
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.models.gpt2 import GPT2Config, build_train_step

    from paddle_tpu.utils import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    cfg = GPT2Config.tiny() if TINY else GPT2Config()
    cfg.dropout = 0.0
    loss_fn, init_params, _ = build_train_step(cfg, remat=False)
    optimizer = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)

    def to_bf16(x):
        return x.astype(jnp.bfloat16) \
            if jnp.issubdtype(x.dtype, jnp.floating) else x

    def amp_loss(p32, data, key):
        pb = jax.tree_util.tree_map(to_bf16, p32)
        return loss_fn(pb, data, key).astype(jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_n(p, s, ids):
        def step(carry, mb):
            p, s = carry
            batch = {"input_ids": mb, "labels": mb}
            loss, grads = jax.value_and_grad(amp_loss)(
                p, batch, jax.random.key(0))
            np_, ns = optimizer.functional_update(p, grads, s)
            return (np_, ns), loss
        (p, s), losses = jax.lax.scan(step, (p, s), ids)
        return p, s, jnp.mean(losses)

    # fixed corpus served through the REAL input pipeline (multiprocess
    # workers + the native byte queue), persistent across epochs
    loader = pio.DataLoader(Corpus(cfg.vocab_size), batch_size=BATCH,
                            shuffle=True, num_workers=2,
                            persistent_workers=True, drop_last=True)
    return (init_params, optimizer, train_n, loader)


def _batches(loader):
    while True:  # epoch-cycling generator
        for b in loader:
            yield np.asarray(b.numpy() if hasattr(b, "numpy") else b)


def run_phase(args):
    import jax

    from paddle_tpu.distributed import checkpoint as dckpt
    from paddle_tpu.utils.watchdog import Watchdog

    os.makedirs(args.dir, exist_ok=True)
    log_path = os.path.join(args.dir, "loss_log.jsonl")
    ckpt_dir = os.path.join(args.dir, "ckpt")
    init_params, optimizer, train_n, loader = _build(args)

    params = init_params()
    opt_state = optimizer.functional_init(params)
    step0 = 0
    if args.phase == "resume":
        like = {"step": 0, "params": params, "opt": opt_state}
        state = dckpt.load(ckpt_dir, like)
        step0 = int(state["step"])
        params, opt_state = state["params"], state["opt"]
        print(f"# resumed from step {step0}", flush=True)

    if args.kill_after_s:
        def killer():
            time.sleep(args.kill_after_s)
            print("# KILL (simulated failure)", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
        threading.Thread(target=killer, daemon=True).start()

    wd = Watchdog(timeout=240, action="abort")
    wd.start()
    gen = _batches(loader)
    t_end = time.time() + args.run_s
    step = step0
    log = open(log_path, "a")
    dispatches = 0
    while time.time() < t_end:
        ids = np.stack([next(gen) for _ in range(INNER)])
        params, opt_state, loss = train_n(params, opt_state, ids)
        loss = float(jax.device_get(loss))
        step += INNER
        dispatches += 1
        wd.beat(step=step, loss=loss)
        log.write(json.dumps({"step": step, "loss": loss,
                              "t": time.time(),
                              "phase": args.phase}) + "\n")
        log.flush()
        if dispatches % CKPT_EVERY == 0:
            t0 = time.time()
            dckpt.save({"step": step, "params": params,
                        "opt": opt_state}, ckpt_dir)
            print(f"# ckpt @ step {step} ({time.time()-t0:.1f}s) "
                  f"loss {loss:.4f}", flush=True)
    wd.stop()
    loader.close()
    print(f"# phase {args.phase} done: steps {step0}->{step}, "
          f"watchdog trips={wd.fired}", flush=True)


def orchestrate(args):
    base = [sys.executable, os.path.abspath(__file__),
            "--dir", args.dir]
    # a reused --dir would append to the old loss log and resume from the
    # old checkpoints — the verification would then read STALE records
    for leftover in ("loss_log.jsonl", "ckpt"):
        path = os.path.join(args.dir, leftover)
        if os.path.exists(path):
            raise SystemExit(
                f"{path} exists: pass a fresh --dir per drill (the "
                f"continuity check must only see this drill's records)")
    print("== phase 1: run until SIGKILL ==", flush=True)
    # own process group: spawn-started DataLoader workers carry a
    # spawn_main argv (a pkill -f on OUR argv would never match them),
    # but they inherit phase 1's pgid — killpg reaps the whole family
    # after the SIGKILL (which skips atexit, orphaning them otherwise)
    p1 = subprocess.Popen(base + ["--phase", "run",
                                  "--run-s", str(args.phase1_s + 600),
                                  "--kill-after-s", str(args.phase1_s)],
                          start_new_session=True)
    rc1 = p1.wait()
    print(f"phase1 rc={rc1} (expect -9)", flush=True)
    assert rc1 == -signal.SIGKILL, rc1
    try:
        os.killpg(p1.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    time.sleep(2)
    print("== phase 2: resume ==", flush=True)
    r2 = subprocess.run(base + ["--phase", "resume",
                                "--run-s", str(args.phase2_s)])
    assert r2.returncode == 0, r2.returncode

    # ---- verify continuity ----
    recs = [json.loads(ln) for ln in
            open(os.path.join(args.dir, "loss_log.jsonl"))]
    run = [r for r in recs if r["phase"] == "run"]
    res = [r for r in recs if r["phase"] == "resume"]
    assert len(run) >= 3 and len(res) >= 3, (
        f"too few dispatches to verify continuity (run={len(run)}, "
        f"resume={len(res)}): lengthen --phase1-s/--phase2-s past the "
        f"compile time")
    resume_step0 = res[0]["step"]
    ckpt_step = resume_step0 - INNER
    # (a) resume restarted from a checkpointed step, not from zero
    assert ckpt_step > 0 and ckpt_step % (INNER * CKPT_EVERY) == 0, \
        resume_step0
    # (b) continuity: first resumed losses sit on the pre-kill trend —
    # compare against the run-phase losses bracketing the ckpt step
    pre = [r["loss"] for r in run
           if ckpt_step - 10 * INNER <= r["step"] <= ckpt_step]
    first_res = np.mean([r["loss"] for r in res[:3]])
    pre_mean = np.mean(pre)
    drift = abs(first_res - pre_mean) / max(pre_mean, 1e-9)
    # (c) the drill actually learned
    improved = res[-1]["loss"] < run[2]["loss"]
    summary = {
        "steps_run": run[-1]["step"], "ckpt_step": ckpt_step,
        "resume_first_loss": float(first_res),
        "pre_kill_loss": float(pre_mean),
        "continuity_drift": float(drift),
        "final_loss": res[-1]["loss"],
        "initial_loss": run[0]["loss"],
        "improved": bool(improved),
    }
    print(json.dumps(summary), flush=True)
    # continuity = the resumed curve CONTINUES the pre-kill trend: it
    # must not jump back up (a from-scratch restart would sit near the
    # initial loss). Progress between the checkpoint and the resume
    # comparison window legitimately moves it DOWN, so only bound above.
    assert first_res < pre_mean * 1.10, summary
    assert first_res < run[0]["loss"] * 0.7, summary  # far below cold
    assert improved, summary
    print("ENDURANCE_OK", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", required=True)
    ap.add_argument("--phase", choices=["run", "resume"], default="run")
    ap.add_argument("--run-s", type=float, default=480)
    ap.add_argument("--kill-after-s", type=float, default=0)
    ap.add_argument("--phase1-s", type=float, default=480)
    ap.add_argument("--phase2-s", type=float, default=360)
    ap.add_argument("--orchestrate", action="store_true")
    a = ap.parse_args()
    if a.orchestrate:
        orchestrate(a)
    else:
        run_phase(a)


if __name__ == "__main__":
    main()
