"""HProt training with an asynchronous checkpoint of the whole state.

Set-up builds the program's model (``models.transformer.LM``) from the
configuration file, makes the weights on the device from the seed in one
jitted call (``bench/ref/stablelm.py``, in the program's parameter
layout), compiles the program's train step (``train.step``, donated, as
``Trainer`` runs it) and drives it through the first ``checked_steps``
steps on the mix's token rows. It records each step's loss and, after
step 1, the norm of each leaf's gradient as the optimizer got it (Adam's
first moment over ``1 - b1``). It opens an ``AsyncCheckpointManager``
and, as its last act, pulls a host copy of the state.

The window's first act saves that state; training goes on, step after
step on fresh rows, until ``--seconds`` have passed and the save is
durable (its manifest committed after fsync), whichever is later.

The check restores the checkpoint through a fresh manager and compares
it bit for bit with the host copy, then runs the plain reference
(``bench/ref/stablelm.py``, float32 at ``highest``) from the same weights
over the same rows for the checked steps, and compares the losses, the
first gradient's leaf norms and the leaf norms of the parameters'
change over those steps.
"""
from __future__ import annotations

import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from ref import stablelm  # noqa: E402
from ref.tokens import Tokens  # noqa: E402

#: leaves whose reference gradient norm is under this share of the
#: median leaf's move by round-off alone under Adam: their change is
#: left out of the comparison
SILENT_GRAD = 1e-3


def relative_gaps(got: dict, want: dict, keys=None) -> dict:
    """``|got - want| / max(|want|, median |want|)`` per key."""
    keys = list(want) if keys is None else list(keys)
    vals = sorted(abs(want[k]) for k in want)
    median = vals[len(vals) // 2] if vals else 0.0
    return {k: abs(got[k] - want[k]) / max(abs(want[k]), median, 1e-30)
            for k in keys}


class Readings:
    """What the checked steps produced: each step's loss, the first
    gradient's leaf norms, and the leaf norms of the change."""

    def __init__(self, losses, grad, change):
        self.losses, self.grad, self.change = losses, grad, change

    def gaps(self, ref: "Readings") -> dict:
        """The numbers compared against ``ref``, and what explains them:
        the loss (printed, not compared), the worst leaf of the first
        gradient and of the change, and the embedding table's change."""
        loss = max(abs(a - b) / abs(b) for a, b in zip(self.losses,
                                                        ref.losses))
        gmed = sorted(ref.grad.values())[len(ref.grad) // 2]
        moving = [k for k, g in ref.grad.items() if g >= SILENT_GRAD * gmed]
        grad = relative_gaps(self.grad, ref.grad)
        change = relative_gaps(self.change, ref.change, moving)

        def top(d):
            return sorted(d.items(), key=lambda kv: -kv[1])[:3]
        return {"loss_rel_gap": loss,
                "grad_norm_gap": max(grad.values()),
                "change_norm_gap": max(change.values()),
                "worst_grad_leaves": top(grad),
                "worst_change_leaves": top(change),
                "embed_change_gap": relative_gaps(
                    self.change, ref.change, ["embed.tok"])["embed.tok"],
                "silent_leaves": sorted(set(ref.grad) - set(moving))}


class Cell:
    ANNOTATIONS = ("train_step", "save")

    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 scratch: str, devices):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.device = devices[0]
        self.root = os.path.join(scratch, "hprot")
        self.opt = {k: v for k, v in config["optimizer"].items()
                    if k != "name"}
        self.tokens = Tokens(config["vocab_size"], traffic["seq_len"],
                             traffic["global_batch"], seed,
                             traffic.get("zipf", 1.1))
        self.checked = traffic["checked_steps"]
        self.ckpt = None
        self.state = None
        self.window_losses: list[float] = []

    # ------------------------------------------------------------ set-up
    def _model(self):
        from repro.models.config import ModelConfig
        from repro.models.transformer import LM
        c = self.cfg
        if c["partial_rotary_factor"] != 1.0 or c["use_qkv_bias"]:
            raise ValueError("the program rotates whole heads and has no "
                             "q/k/v bias; the configuration must say so")
        return LM(ModelConfig(
            name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            mlp_act="swiglu", norm="layernorm",
            rope_theta=float(c["rope_theta"]),
            tie_embeddings=c["tie_word_embeddings"],
            compute_dtype=c["torch_dtype"], param_dtype=c["param_dtype"],
            remat=c["remat"]))

    def _init(self):
        """``key -> program state``: the seeded weights and zero Adam
        moments, made on the device in one jitted call."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg

        def make(key):
            params = stablelm.init_params(cfg, key)
            zeros = jax.tree.map(jnp.zeros_like, params)
            return {"params": params, "mu": zeros,
                    "nu": jax.tree.map(jnp.zeros_like, params),
                    "step": jnp.zeros((), jnp.int32)}
        return jax.jit(make)

    def batch(self, i: int):
        import jax
        b = self.tokens.batch_at(i)
        return {k: jax.device_put(v, self.device) for k, v in b.items()}

    def setup(self) -> None:
        import jax

        from repro.ckpt import AsyncCheckpointManager
        from repro.train import optim
        from repro.train import step as step_lib
        lm = self._model()
        want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                            lm.abstract_params())
        have = jax.tree.map(lambda s: (tuple(s), "float32"),
                            stablelm.shapes(self.cfg),
                            is_leaf=lambda x: isinstance(x, tuple))
        if want != have:
            raise ValueError(f"parameter layout differs from the program's: "
                             f"{have} vs {want}")
        t0 = time.perf_counter()
        self.init = self._init()
        self.step_fn = jax.jit(
            step_lib.make_train_step(lm, optim.OptConfig(**self.opt)),
            donate_argnums=0)
        state = self.init(stablelm.seed_key(self.seed))
        losses = []
        for i in range(self.checked):
            state, m = self.step_fn(state, self.batch(i))
            losses.append(float(m["loss"]))
            if i == 0:
                b1 = 1.0 - self.opt["b1"]
                grad = {k: v / b1 for k, v in
                        stablelm.leaf_norms(state["mu"]).items()}
        self.program = Readings(losses, grad, None)
        self.state = state
        t1 = time.perf_counter()
        # the save's device-side copies, warmed at the state's shapes
        for leaf in jax.tree.leaves(state):
            jax.numpy.array(leaf.addressable_shards[0].data).delete()
        ck = self.cfg["checkpoint"]
        self.ckpt = AsyncCheckpointManager(
            self.root, ncf=ck["ncf"], delta_every=ck["delta_every"],
            lane_backend=ck["lane_backend"])
        self.host_state = jax.device_get(state)
        print(f"[setup] init_and_steps_s={t1 - t0!r} "
              f"save_warm_and_pull_s={time.perf_counter() - t1!r}",
              file=sys.stderr, flush=True)

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
        waiter = threading.Thread(target=wait, name="bench-durable")
        waiter.start()
        i = self.checked
        while True:
            with jax.profiler.TraceAnnotation("train_step"):
                self.state, m = self.step_fn(self.state, self.batch(i))
                self.window_losses.append(float(m["loss"]))
            i += 1
            now = time.perf_counter()
            if now - t0 >= seconds and durable:
                break
        waiter.join()
        if "error" in durable:
            raise durable["error"]
        steps = len(self.window_losses)
        tokens = steps * self.traffic["seq_len"] * self.traffic["global_batch"]
        return {"train_tokens_per_s": tokens / (now - t0),
                "ckpt_durable_s": durable["t"] - t0}

    def counts(self) -> dict:
        return {"steps": len(self.window_losses),
                "seq_len": self.traffic["seq_len"], "config": self.cfg}

    # ------------------------------------------------------------- check
    def restore_mismatches(self) -> tuple[int, int]:
        """``(tensors restored, tensors not bit-equal to the saved
        state)`` through a fresh manager, as a restarted job restores."""
        import jax
        import numpy as np

        from repro.ckpt import AsyncCheckpointManager
        ck = self.cfg["checkpoint"]
        mgr = AsyncCheckpointManager(self.root, ncf=ck["ncf"],
                                     lane_backend=ck["lane_backend"])
        try:
            if mgr.latest_step() != self.checked:
                return 0, len(jax.tree.leaves(self.host_state))
            sharding = jax.sharding.SingleDeviceSharding(self.device)
            template = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), np.asarray(x).dtype, sharding=sharding),
                self.host_state)
            restored, _ = mgr.restore(template)
        finally:
            mgr.close()
        bad = n = 0
        for got, want in zip(jax.tree.leaves(restored),
                             jax.tree.leaves(self.host_state)):
            g, w = np.asarray(got), np.asarray(want)
            n += 1
            bad += int(g.dtype != w.dtype or g.shape != w.shape
                       or g.tobytes() != w.tobytes())
            got.delete()
        return n, bad

    def reference(self, compute=None) -> Readings:
        """The checked steps through the plain reference, from the same
        weights and rows; ``compute`` lowers its precision (the
        control)."""
        import jax
        import jax.numpy as jnp
        compute = compute or jnp.float32
        step = stablelm.make_step(self.cfg, self.opt, compute)
        s = self.init(stablelm.seed_key(self.seed))
        params, mu, nu = s["params"], s["mu"], s["nu"]
        losses = []
        for i in range(self.checked):
            b = self.batch(i)
            params, mu, nu, value = step(params, mu, nu, jnp.int32(i + 1),
                                         b["tokens"], b["labels"])
            losses.append(float(value))
            if i == 0:
                b1 = 1.0 - self.opt["b1"]
                grad = {k: v / b1 for k, v in stablelm.leaf_norms(mu).items()}
        del mu, nu
        p0 = self.init(stablelm.seed_key(self.seed))["params"]
        change = stablelm.leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        return Readings(losses, grad, change)

    def program_change(self) -> dict:
        """Leaf norms of the program's change over the checked steps,
        from the host copy of its state."""
        import jax
        import jax.numpy as jnp
        p0 = self.init(stablelm.seed_key(self.seed))["params"]
        p3 = jax.device_put(self.host_state["params"], self.device)
        return stablelm.leaf_norms(jax.tree.map(jnp.subtract, p3, p0))

    def verify(self):
        import math

        import jax
        tel = self.ckpt.telemetry()
        self.ckpt.close()
        self.ckpt = None
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None
        t0 = time.perf_counter()
        n, bad = self.restore_mismatches()
        t1 = time.perf_counter()
        self.program.change = self.program_change()
        gaps = self.program.gaps(self.reference())
        t2 = time.perf_counter()
        limits = self.traffic["limits"]
        nonfinite = sum(not math.isfinite(x) for x in self.window_losses)
        checks = [(k, gaps[k], limits[k])
                  for k in ("grad_norm_gap", "change_norm_gap",
                            "embed_change_gap")]
        checks += [("restore_mismatches", bad, 0),
                   ("save_errors", tel["errors"], 0),
                   ("window_nonfinite_losses", nonfinite, 0)]
        print(f"[hprot] loss_rel_gap={gaps['loss_rel_gap']!r} (not "
              f"compared) worst_grad_leaves={gaps['worst_grad_leaves']} "
              f"worst_change_leaves={gaps['worst_change_leaves']} "
              f"silent_leaves={gaps['silent_leaves']} "
              f"program_losses={self.program.losses} "
              f"window_steps={len(self.window_losses)} "
              f"restore_s={t1 - t0!r} reference_s={t2 - t1!r}",
              file=sys.stderr, flush=True)
        attempted = n + 3
        failed = bad + sum(v > lim for _, v, lim in checks[:3])
        return checks, attempted, failed

    def close(self) -> None:
        if self.ckpt is not None:
            self.ckpt.close()
            self.ckpt = None
