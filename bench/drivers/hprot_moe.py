"""HProt training of a DeepSeek-V2 MoE with an async checkpoint of the
whole state, under a full HBM.

The ``hprot`` driver's cell, for the MoE: set-up builds the program's
model (``models.transformer.LM`` from ``configs.deepseek_v2_lite.from_hf``
on the configuration file), makes the weights on the device from the seed
(``bench/ref/deepseek_v2.py``, in the program's layout), compiles the
program's donated train step and drives it through the first
``checked_steps`` steps, recording each step's loss, the first gradient's
norms (leaf by leaf, each held expert its own) and the step's MoE
counters. The window saves the state as its first act and trains on
until ``--seconds`` have passed and the save is durable, with one step
queued behind the one running (a trainer that reads each step's loss a
step late). The train state
and the step's activations fill most of the chip, so the save's cut
crosses most of the state to the host inside the stall
(``AsyncCheckpointManager._cut_budget``).

The check restores the checkpoint through a fresh manager bit for bit,
runs the reference's checked steps one sequence at a time (float32 at
``highest``) from the same weights over the same rows, compares gradient
and change norms, and compares each MoE layer's top-k expert sets at step
1 (``routing_flip_share``); every routed assignment must have been
computed (``dropped_assignments``).
"""
from __future__ import annotations

import math
import os
import resource
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from drivers import hprot  # noqa: E402
from ref import deepseek_v2 as ref  # noqa: E402

COUNTERS = ("moe_assignments_held", "moe_load_max_over_mean", "moe_dropped")


def limited_checks(gaps: dict, limits: dict, flips=None) -> list:
    """The checks that have a limit, ``(name, value, limit)``: the gaps
    of ``hprot.Readings.gaps`` and, where given, the routing flips. The
    cell's check and the controls (``bench/tools/controls_moe.py``)
    judge by this one list."""
    checks = [(k, gaps[k], limits[k])
              for k in ("grad_norm_gap", "change_norm_gap", "embed_change_gap")]
    if flips is not None:
        checks.append(("routing_flip_share", flips,
                       limits["routing_flip_share"]))
    return checks


class Cell(hprot.Cell):
    ANNOTATIONS = ("train_step", "save")

    def __init__(self, config, traffic, *, seed, scratch, devices):
        super().__init__(config, traffic, seed=seed, scratch=scratch,
                         devices=devices)
        self.step_counts: list[dict] = []

    # ------------------------------------------------------------ set-up
    def _model(self):
        from repro.configs.deepseek_v2_lite import from_hf
        from repro.models.transformer import LM
        c = self.cfg
        return LM(from_hf(c, name=c["name"], compute_dtype=c["torch_dtype"],
                          param_dtype=c["param_dtype"], remat=c["remat"],
                          attn_chunk=c["attention_chunk"]))

    def _init(self):
        import jax
        import jax.numpy as jnp
        cfg = self.cfg

        def make(key):
            params = ref.init_params(cfg, key)
            return {"params": params,
                    "mu": jax.tree.map(jnp.zeros_like, params),
                    "nu": jax.tree.map(jnp.zeros_like, params),
                    "step": jnp.zeros((), jnp.int32)}
        return jax.jit(make)

    def _dispatch(self, i: int) -> dict:
        """Queues the program's step on batch ``i``; returns its loss and
        counters, still on the device."""
        self.state, m = self.step_fn(self.state, self.batch(i))
        return {k: m[k] for k in ("loss", *COUNTERS)}

    def _record(self, m: dict) -> float:
        """Waits for a queued step's loss and counters; records the
        counters and returns the loss."""
        import jax
        got = jax.device_get(m)
        self.step_counts.append({k: float(got[k]) for k in COUNTERS})
        return float(got["loss"])

    def _step(self, i: int) -> float:
        return self._record(self._dispatch(i))

    def setup(self) -> None:
        import jax

        from repro.ckpt import AsyncCheckpointManager
        from repro.train import optim
        from repro.train import step as step_lib
        self.lm = self._model()
        want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                            self.lm.abstract_params())
        have = jax.tree.map(lambda s: (tuple(s), "float32"),
                            ref.shapes(self.cfg),
                            is_leaf=lambda x: isinstance(x, tuple))
        if want != have:
            raise ValueError(f"parameter layout differs from the program's: "
                             f"{have} vs {want}")
        t0 = time.perf_counter()
        self.init = self._init()
        self.step_fn = jax.jit(
            step_lib.make_train_step(self.lm, optim.OptConfig(**self.opt)),
            donate_argnums=0)
        self.state = self.init(ref.seed_key(self.seed))
        losses = []
        for i in range(self.checked):
            losses.append(self._step(i))
            if i == 0:
                b1 = 1.0 - self.opt["b1"]
                grad = {k: v / b1 for k, v in
                        ref.leaf_norms(self.state["mu"]).items()}
        self.program = hprot.Readings(losses, grad, None)
        t1 = time.perf_counter()
        # the save's device-side copies, warmed at the state's shapes
        for leaf in jax.tree.leaves(self.state):
            jax.numpy.array(leaf.addressable_shards[0].data).delete()
        ck = self.cfg["checkpoint"]
        self.ckpt = AsyncCheckpointManager(
            self.root, ncf=ck["ncf"], delta_every=ck["delta_every"],
            lane_backend=ck["lane_backend"])
        self.host_state = jax.device_get(self.state)
        print(f"[setup] init_and_steps_s={t1 - t0!r} "
              f"save_warm_and_pull_s={time.perf_counter() - t1!r} "
              f"step_counts={self.step_counts}", file=sys.stderr, flush=True)

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> dict:
        import jax
        t0 = time.perf_counter()
        durable: dict = {}

        def wait():
            try:
                self.ckpt.wait()
                durable["t"] = time.perf_counter()
            except BaseException as e:   # noqa: BLE001 — re-raised below
                durable["error"] = e
        with jax.profiler.TraceAnnotation("save"):
            self.ckpt.save(self.checked, self.state)
        self.stall_s = time.perf_counter() - t0
        waiter = threading.Thread(target=wait, name="bench-durable")
        waiter.start()
        # one step queued ahead: the host reads a step's loss while the
        # next one runs, so the gather thread's host work (encode, CRC)
        # delays a read, not the device
        i, queued = self.checked, None
        while True:
            with jax.profiler.TraceAnnotation("train_step"):
                m = self._dispatch(i)
                if queued is not None:
                    self.window_losses.append(self._record(queued))
            queued = m
            i += 1
            now = time.perf_counter()
            if self.window_losses and now - t0 >= seconds and durable:
                break
        with jax.profiler.TraceAnnotation("train_step"):
            self.window_losses.append(self._record(queued))
        now = time.perf_counter()
        waiter.join()
        if "error" in durable:
            raise durable["error"]
        steps = len(self.window_losses)
        tokens = steps * self.traffic["seq_len"] * self.traffic["global_batch"]
        return {"train_tokens_per_s": tokens / (now - t0),
                "ckpt_durable_s": durable["t"] - t0}

    def counts(self) -> dict:
        window = self.step_counts[self.checked:]
        n = max(1, len(window))
        return {"steps": len(self.window_losses),
                "seq_len": self.traffic["seq_len"],
                "global_batch": self.traffic["global_batch"],
                "config": self.cfg,
                "moe_assignments_held": sum(
                    c["moe_assignments_held"] for c in window) / n,
                "moe_load_max_over_mean": max(
                    (c["moe_load_max_over_mean"] for c in window),
                    default=0.0),
                "moe_dropped": sum(c["moe_dropped"] for c in self.step_counts)}

    # ------------------------------------------------------------- check
    def reference(self, compute=None, batch=None) -> hprot.Readings:
        """The checked steps through the plain reference, from the same
        weights and rows; ``compute`` lowers its precision (the control),
        ``batch`` replaces the rows (a control too)."""
        import jax
        import jax.numpy as jnp
        compute = compute or jnp.float32
        batch = batch or self.batch
        step = ref.make_step(self.cfg, self.opt, compute)
        s = self.init(ref.seed_key(self.seed))
        params, mu, nu = s["params"], s["mu"], s["nu"]
        losses = []
        for i in range(self.checked):
            b = batch(i)
            params, mu, nu, value = step(params, mu, nu, jnp.int32(i + 1),
                                         b["tokens"], b["labels"])
            losses.append(float(value))
            if i == 0:
                b1 = 1.0 - self.opt["b1"]
                grad = {k: v / b1 for k, v in ref.leaf_norms(mu).items()}
        del mu, nu
        p0 = self.init(ref.seed_key(self.seed))["params"]
        change = ref.leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        return hprot.Readings(losses, grad, change)

    def program_change(self) -> dict:
        import jax
        import jax.numpy as jnp
        p0 = self.init(ref.seed_key(self.seed))["params"]
        p3 = jax.device_put(self.host_state["params"], self.device)
        return ref.leaf_norms(jax.tree.map(jnp.subtract, p3, p0))

    def routing_flip_share(self, compute=None) -> float:
        """Share of (MoE layer, token) pairs at step 1 whose top-k expert
        set differs between the program and the reference (``compute``
        lowers the reference's precision)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        compute = compute or jnp.float32
        params = self.init(ref.seed_key(self.seed))["params"]
        tokens = self.batch(0)["tokens"]
        got = np.sort(np.asarray(jax.jit(self.lm.routes)(params, tokens)), -1)
        one = jax.jit(lambda p, t: ref.routes(p, t, self.cfg, compute))
        want = np.sort(np.concatenate(
            [np.asarray(one(params, tokens[i:i + 1]))
             for i in range(tokens.shape[0])], axis=1), -1)
        return float(np.mean(np.any(got != want, axis=-1)))

    def verify(self):
        import jax
        tel = self.ckpt.telemetry()
        self.ckpt.close()
        self.ckpt = None
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None
        # the reference fills the HBM the step's state and program held:
        # drop the step's compiled program too
        jax.clear_caches()
        t0 = time.perf_counter()
        n, bad = self.restore_mismatches()
        t1 = time.perf_counter()
        self.program.change = self.program_change()
        gaps = self.program.gaps(self.reference())
        flips = self.routing_flip_share()
        t2 = time.perf_counter()
        limits = self.traffic["limits"]
        nonfinite = sum(not math.isfinite(x) for x in self.window_losses)
        dropped = int(sum(c["moe_dropped"] for c in self.step_counts))
        checks = limited_checks(gaps, limits, flips)
        checks += [("restore_mismatches", bad, 0),
                   ("save_errors", tel["errors"], 0),
                   ("window_nonfinite_losses", nonfinite, 0),
                   ("dropped_assignments", dropped, 0)]
        window = self.step_counts[self.checked:]
        load = [round(c["moe_load_max_over_mean"], 2) for c in window]
        held = [int(c["moe_assignments_held"]) for c in window]
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        print(f"[hprot_moe] loss_rel_gap={gaps['loss_rel_gap']!r} (not "
              f"compared) worst_grad_leaves={gaps['worst_grad_leaves']} "
              f"worst_change_leaves={gaps['worst_change_leaves']} "
              f"silent_leaves={len(gaps['silent_leaves'])} "
              f"program_losses={self.program.losses} "
              f"window_steps={len(self.window_losses)} "
              f"stall_s={self.stall_s!r} "
              f"cut_host_bytes={tel['cut_host_bytes']} "
              f"counts={ {k: v for k, v in self.counts().items() if k != 'config'} } "
              f"window_load_max_over_mean={load} "
              f"window_assignments_held={held} "
              f"host_rss_peak_bytes={rss} "
              f"memory_stats={self.device.memory_stats()} "
              f"restore_s={t1 - t0!r} reference_s={t2 - t1!r}",
              file=sys.stderr, flush=True)
        attempted = n + 5
        failed = bad + sum(v > lim for _, v, lim in checks[:4])
        return checks, attempted, failed
