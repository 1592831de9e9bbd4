"""ZeRO-Infinity: parameters + optimizer state on NVMe, layerwise execution.

Counterpart of the reference's parameter swapper + stage-3 offload stack
(``swap_tensor/partitioned_param_swapper.py:1`` — params with
``remote_device='nvme'`` stream through GPU per-module;
``zero/partition_parameters.py:617``). The TPU redesign: instead of module
hooks swapping tensors under a monolithic autograd graph, the TRAINING STEP
itself is host-orchestrated over per-layer jitted programs:

  fwd:  embed → [upload layer l weights from NVMe → one-block program]×L
        (boundary activations parked in host RAM)
  loss: final-norm + chunked CE (+ its grads wrt shared params and x_L)
  bwd:  reversed [upload layer l → one-block VJP]×L, per-layer grads landing
        in host RAM
  step: global-norm clip, then the windowed NVMe Adam (optimizer_swapper)
        updates every tensor ON DISK; only the small shared subtree returns
        to HBM.

Peak HBM = one layer's weights + one activation + the block program's temps:
models whose parameters exceed HBM train. Peak host RAM = activations +
grads, windowed state. Disk traffic per step = params read twice + optimizer
state read+written once.

Supports the GPT2Model family (all variant switches) — the stacked-blocks +
``_block`` protocol; loss/embed hooks come from PipelinedGPT2's stage fns.

Deployment note: this path round-trips layer weights/activations through the
CONTROLLER's RAM (np.asarray / device_put) — the Python controller sits on
the TPU VM next to the chip, so a 1.3B-param step costs ~10GB of host-link
traffic. Not measured on a chip in this round; numerics are pinned by the
CPU-backend test (tests/unit/test_offload.py::TestZeroInfinityParams).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger


def _host_jit(label, fn):
    """The NVMe layerwise path runs single-device programs (one layer in
    HBM at a time); placements are explicitly inherited — stated through
    sharded_jit so the program table and the unspecified-jit lint see
    them like every other engine program."""
    from deepspeed_tpu.sharding import INHERIT, sharded_jit

    return sharded_jit(fn, label=label, donate_argnums=(),
                       in_shardings=INHERIT, out_shardings=INHERIT)


class ZeroInfinityEngine:
    """Layerwise NVMe-resident trainer (params + Adam state on disk)."""

    def __init__(self, model, ds_config, mesh=None):
        from deepspeed_tpu.models.gpt2 import GPT2Model
        from deepspeed_tpu.models.gpt2_pipe import PipelinedGPT2
        from deepspeed_tpu.runtime.swap_tensor.optimizer_swapper import \
            SwappedOptimizer

        if not isinstance(model, GPT2Model) or isinstance(model, PipelinedGPT2):
            raise NotImplementedError(
                "ZeRO-Infinity param offload drives the stacked-block "
                "GPT2Model family; got " + type(model).__name__)
        if model.config.dropout:
            raise NotImplementedError("param-NVMe training with dropout")
        self.model = model
        self.config = model.config
        # embed/final-norm/chunked-CE hooks over the shared subtree are the
        # pipeline executor's stage fns — same decomposition, reused
        self._hooks = PipelinedGPT2(model.config, num_stages=1, num_micro=1)
        self._cfg = ds_config
        off = ds_config.zero_config.offload_param
        folder = (off.nvme_path if off and off.nvme_path else
                  "/tmp/ds_tpu_nvme_params")
        if jax.process_count() > 1:
            raise NotImplementedError(
                "layerwise param-NVMe is single-host (one controller drives "
                "the per-layer programs); shard data-parallel across hosts "
                "with offload_optimizer=nvme instead")
        opt_params = dict(ds_config.optimizer_params or {})
        self.optimizer = SwappedOptimizer(
            swap_folder=folder,
            optimizer_name=ds_config.optimizer_name or "adamw",
            optimizer_params=opt_params,
            aio_config=ds_config.aio_config.model_dump(),
            buffer_count=(off.buffer_count if off else 5))
        self._lr = float(opt_params.get("lr", 1e-3))
        # ds_config scheduler drives the per-step lr exactly as in the main
        # engine (the swapped Adam takes lr per step)
        from deepspeed_tpu.runtime.lr_schedules import build_lr_schedule

        self.lr_scheduler = None
        if ds_config.scheduler_name:
            self.lr_scheduler = build_lr_schedule(
                ds_config.scheduler_name,
                dict(ds_config.scheduler_params or {}))
        self.gas = int(ds_config.gradient_accumulation_steps or 1)
        self.grad_clip = float(ds_config.gradient_clipping or 0.0)
        self.global_steps = 0
        self._compiled: Dict[str, Any] = {}

        # seed masters+moments on NVMe leaf by leaf: peak HBM during init is
        # ONE stacked leaf (XLA DCEs the initializer's other leaves), peak
        # host RAM one leaf per write window
        c = self.config
        L = c.n_layer
        key = jax.random.PRNGKey(ds_config.seed)
        full_shapes = jax.eval_shape(model.init_params, key)
        self._blk_shapes = {k: v for k, v in full_shapes["blocks"].items()}
        named: Dict[str, np.ndarray] = {}
        n_elems = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(full_shapes))
        from deepspeed_tpu.accelerator import get_accelerator

        if n_elems * 4 < 0.5 * get_accelerator().hbm_bytes():
            # the fp32 tree fits next to nothing else at init time: ONE
            # compile, then slice on host (instead of one leaf-extractor
            # compile per stacked leaf)
            tree = _host_jit("infinity/init_params", model.init_params)(key)
            self.shared = {n: jnp.asarray(np.asarray(v))
                           for n, v in tree.items() if n != "blocks"}
            for leaf_name, leaf in tree["blocks"].items():
                full = np.asarray(leaf, dtype=np.float32)
                for l in range(L):
                    named[f"layer{l:03d}/{leaf_name}"] = full[l]
            del tree
        else:
            # >HBM model: leaf-at-a-time (XLA DCEs the other leaves)
            shared_fn = _host_jit(
                "infinity/init_shared",
                lambda k: {n: v for n, v in model.init_params(k).items()
                           if n != "blocks"})
            self.shared = {n: jnp.asarray(v) for n, v in shared_fn(key).items()}
            for leaf_name in self._blk_shapes:
                leaf_fn = _host_jit(
                    f"infinity/init_leaf[{leaf_name}]",
                    lambda k, _n=leaf_name: model.init_params(k)["blocks"][_n])
                full = np.asarray(leaf_fn(key), dtype=np.float32)
                for l in range(L):
                    named[f"layer{l:03d}/{leaf_name}"] = full[l]
                del full
        for n, v in self.shared.items():
            named[f"shared/{n}"] = np.asarray(v, dtype=np.float32)
        self.optimizer.init_from_params(named)
        del named
        log_dist(f"ZeRO-Infinity: {n_elems/1e6:.1f}M params + Adam state on "
                 f"NVMe ({folder}); layerwise execution, peak HBM ≈ 1 layer",
                 ranks=[0])

    # --------------------------------------------------------------- helpers
    def _read_layer(self, l: int) -> Dict[str, jnp.ndarray]:
        """Layer l's compute-dtype weights, read from the NVMe masters."""
        sw = self.optimizer.swapper
        names = [f"layer{l:03d}/{k}" for k in self._blk_shapes]
        for n in names:
            sw.swap_in(f"{n}#w", async_op=True)
        sw.synchronize()
        out = {}
        for k in self._blk_shapes:
            n = f"layer{l:03d}/{k}"
            # upload in the COMPUTE dtype: fp32 would double the per-layer
            # HBM + link traffic on the path whose point is one-layer peak
            out[k] = jnp.asarray(sw.retrieve(f"{n}#w"), dtype=self.config.dtype)
            sw.release(f"{n}#w")
        return out

    def _jit(self, name, fn):
        if name not in self._compiled:
            self._compiled[name] = _host_jit(f"infinity/{name}", fn)
        return self._compiled[name]

    # ------------------------------------------------------------ train step
    def train_batch(self, batch) -> jnp.ndarray:
        m, c = self._hooks, self.config
        ids = jnp.asarray(np.asarray(
            batch["input_ids"] if isinstance(batch, dict) else batch))
        T = ids.shape[1]
        L = c.n_layer

        embed = self._jit("embed", lambda sh, i: m._first_stage_fn(sh, i, None))
        block = self._jit("block", lambda blk, x, rope: m._block(x, blk, None, rope))

        def block_vjp(blk, x, rope, dy):
            _, pull = jax.vjp(lambda b, xx: m._block(xx, b, None, rope), blk, x)
            return pull(dy)

        blockb = self._jit("block_vjp", block_vjp)

        def last_loss(sh, x, mb):
            return m._last_stage_loss_fn(sh, x, mb)

        lastg = self._jit("last_grads",
                          jax.value_and_grad(last_loss, argnums=(0, 1)))

        def embed_vjp(sh, i, dx):
            _, pull = jax.vjp(lambda s: m._first_stage_fn(s, i, None), sh)
            return pull(dx)[0]

        embedb = self._jit("embed_vjp", embed_vjp)

        rope = m._rope_tables(jnp.arange(T))
        gas = self.gas
        if ids.shape[0] % gas:
            raise ValueError(f"batch rows {ids.shape[0]} not divisible by "
                             f"gradient_accumulation_steps {gas}")

        def micro_slice(obj, g):
            rows = ids.shape[0] // gas
            sl = slice(g * rows, (g + 1) * rows)
            if isinstance(obj, dict):
                return {k: np.asarray(v)[sl] for k, v in obj.items()}
            return np.asarray(obj)[sl]

        grads: Dict[str, np.ndarray] = {}
        losses = []
        for g in range(gas):
            mb = micro_slice(batch if isinstance(batch, dict) else ids, g)
            mids = jnp.asarray(mb["input_ids"] if isinstance(mb, dict) else mb)
            # ---- forward: boundary activations parked on host
            x = embed(self.shared, mids)
            acts: List[np.ndarray] = []
            for l in range(L):
                blk = self._read_layer(l)
                acts.append(np.asarray(x))
                x = block(blk, x, rope)
            # ---- loss + head/embedding grads
            loss, (dshared, dx) = lastg(self.shared, x, mb)
            losses.append(float(loss))
            # ---- backward layer by layer
            for l in reversed(range(L)):
                blk = self._read_layer(l)
                x_l = jnp.asarray(acts[l])
                dblk, dx = blockb(blk, x_l, rope, dx)
                for k, v in dblk.items():
                    key = f"layer{l:03d}/{k}"
                    v = np.asarray(v, dtype=np.float32)
                    grads[key] = grads.get(key, 0.0) + v
            demb = embedb(self.shared, mids, dx)
            add = self._jit("acc", lambda a, b: jax.tree.map(
                lambda p, q: p.astype(jnp.float32) + q.astype(jnp.float32), a, b))
            dshared = add(dshared, demb)
            for n, v in dshared.items():
                key = f"shared/{n}"
                grads[key] = grads.get(key, 0.0) + np.asarray(v, np.float32)
        if gas > 1:
            for k in grads:
                grads[k] = grads[k] / gas
        loss = jnp.float32(np.mean(losses))

        # ---- global-norm clip + windowed NVMe Adam over everything
        sq = sum(float(np.sum(np.square(g))) for g in grads.values())
        gnorm = float(np.sqrt(sq))
        scale = 1.0
        if self.grad_clip > 0 and gnorm > self.grad_clip:
            scale = self.grad_clip / (gnorm + 1e-6)
        lr = (float(self.lr_scheduler.lr_at(self.global_steps))
              if self.lr_scheduler is not None else self._lr)
        new_masters = self.optimizer.step(grads, lr=lr, grad_scale=scale)
        self.shared = {n: jnp.asarray(new_masters[f"shared/{n}"])
                       for n in self.shared}
        # drop layer masters from host RAM immediately (state lives on disk)
        del new_masters
        self.global_steps += 1
        return loss

    def train_batch_size(self) -> int:
        return int(self._cfg.train_batch_size)

    # ------------------------------------------------------------ checkpoint
    def save_checkpoint(self, save_dir: str, tag=None, client_state=None,
                        save_latest: bool = True) -> bool:
        """Snapshot the NVMe state (masters + moments) + step + shared tree.

        Swap files are COPIED (not hardlinked): the aio layer pwrites swap
        files in place, so a link-based snapshot would alias future training
        writes and silently corrupt the checkpoint.
        """
        import json
        import shutil

        tag = tag or f"global_step{self.global_steps}"
        path = os.path.join(os.path.abspath(save_dir), str(tag))
        os.makedirs(path, exist_ok=True)
        self.optimizer.swapper.synchronize()
        src = self.optimizer.swapper.swap_folder
        for fname in os.listdir(src):
            shutil.copy2(os.path.join(src, fname), os.path.join(path, fname))
        np.savez(os.path.join(path, "shared.npz"),
                 **{n: np.asarray(v) for n, v in self.shared.items()})
        with open(os.path.join(path, "client_state.json"), "w") as f:
            json.dump({"tag": tag, "global_steps": self.global_steps,
                       "optimizer_step_count": self.optimizer.step_count,
                       "client_state": client_state or {}}, f, default=str)
        if save_latest:
            with open(os.path.join(os.path.abspath(save_dir), "latest"), "w") as f:
                f.write(str(tag))
        log_dist(f"ZeRO-Infinity: saved checkpoint {tag} to {save_dir}",
                 ranks=[0])
        return True

    def load_checkpoint(self, load_dir: str, tag=None, **_):
        import json
        import shutil

        if tag is None:
            latest = os.path.join(os.path.abspath(load_dir), "latest")
            with open(latest) as f:
                tag = f.read().strip()
        path = os.path.join(os.path.abspath(load_dir), str(tag))
        dst = self.optimizer.swapper.swap_folder
        self.optimizer.swapper.synchronize()
        for fname in os.listdir(path):
            if fname in ("shared.npz", "client_state.json"):
                continue
            shutil.copy2(os.path.join(path, fname), os.path.join(dst, fname))
        shared = np.load(os.path.join(path, "shared.npz"))
        self.shared = {n: jnp.asarray(shared[n]) for n in shared.files}
        with open(os.path.join(path, "client_state.json")) as f:
            meta = json.load(f)
        self.global_steps = int(meta["global_steps"])
        self.optimizer.step_count = int(meta["optimizer_step_count"])
        log_dist(f"ZeRO-Infinity: loaded checkpoint {tag} from {load_dir}",
                 ranks=[0])
        return path, meta.get("client_state", {})

    # -------------------------------------------------- full-tree export
    def gather_params(self) -> Dict[str, Any]:
        """Materialize the full fp32 tree (consolidation/eval on models that
        DO fit; raises naturally on allocation if they don't)."""
        L = self.config.n_layer
        layers = [self._read_layer(l) for l in range(L)]
        blocks = {k: np.stack([np.asarray(layer[k]) for layer in layers])
                  for k in self._blk_shapes}
        out = {n: np.asarray(v) for n, v in self.shared.items()}
        out["blocks"] = blocks
        return out
