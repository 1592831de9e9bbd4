"""Checkpoint save/load for the training engine.

Counterpart of the reference's engine checkpoint path (engine.py
save_checkpoint:2841 / load_checkpoint:2536, CheckpointEngine ABC
runtime/checkpoint_engine/checkpoint_engine.py:9). Layout mirrors the
reference's tag-directory scheme:

    <save_dir>/<tag>/            sharded orbax state (params/master/opt/scaler)
    <save_dir>/<tag>/client_state.json
    <save_dir>/latest             file containing the newest tag

Sharded-by-construction: orbax writes each host's shards (OCDBT), and on load
restores directly into the engine's current ShardingPlan — which is how
"universal checkpointing" (reference checkpoint/universal_checkpoint.py:12)
falls out for free on TPU: a checkpoint saved at one dp/tp degree reshards on
load to any other, because placement is metadata, not file layout.
"""

from __future__ import annotations

import io
import json
import os
from typing import Any, Optional

import jax
import numpy as np

from deepspeed_tpu.resilience import chaos as _chaos
from deepspeed_tpu.resilience.fsio import atomic_write_bytes, atomic_write_text
from deepspeed_tpu.resilience.manifest import (MANIFEST_NAME, candidate_tags,
                                               verify_tag, write_manifest)
from deepspeed_tpu.resilience.retry import NO_RETRY, RetryPolicy, retry
from deepspeed_tpu.utils.logging import log_dist, logger


def _ckpt_dir(save_dir: str, tag: str) -> str:
    return os.path.join(os.path.abspath(save_dir), str(tag))


class CheckpointLayoutError(ValueError):
    """A checkpoint's recorded model layout (head grouping) does not match
    the live engine's. Param shapes are head-count invariant, so without
    this guard a checkpoint trained under one attention grouping loads
    silently and produces different outputs under another. NEVER demoted
    to the next candidate by the restore ladder — every candidate of the
    same run shares the layout, so walking back would just repeat the
    mismatch against an older step."""


# THE emergency-tag detection rule (tier-1 payload file), defined here —
# not in resilience/rewind — because the restore ladder, ds_resize plan
# and ds_report must classify tags WITHOUT importing the rewind module
# (the strict no-op contract keeps it unloaded when the block is absent);
# rewind re-exports these as its own names.
REWIND_STATE_FILE = os.path.join("state", "rewind_state.npz")


def is_emergency_tag(tag_dir: str) -> bool:
    """Does this tag directory hold a tier-1 emergency snapshot (npz
    payload) rather than an orbax state tree?"""
    return os.path.isfile(os.path.join(tag_dir, REWIND_STATE_FILE))


def world_signature(engine) -> dict:
    """The facts that define a TrainState's placement world: dp degree,
    backend device count, and the engine mesh's full named shape. Stamped
    into every snapshot tier (RAM / emergency / ordinary client_state) so
    a restore knows whether it is a same-world reload or a RESIZE."""
    import jax as _jax

    return {
        "dp_world_size": int(engine.dp_world_size),
        "device_count": int(len(_jax.devices())),
        "mesh_shape": sorted((str(k), int(v))
                             for k, v in dict(engine.mesh.shape).items()),
    }


def world_device_count(world: Optional[dict]) -> Optional[int]:
    """Mesh device count of a (possibly JSON-round-tripped) world
    signature — the ``from_world``/``to_world`` number a resize event is
    priced in; None when the signature is absent/unparsable."""
    if not isinstance(world, dict):
        return None
    try:
        shape = world.get("mesh_shape") or []
        if not shape:
            return None         # a world with no mesh axes is unparsable
        n = 1
        for _, size in shape:
            n *= int(size)
        return n if n > 0 else None
    except (TypeError, ValueError):
        return None


def tag_world(tag_dir: str) -> Optional[int]:
    """Mesh device count a tag was SAVED under, read from its
    ``client_state.json`` world signature — the one read ``ds_resize
    plan`` and ``ds_report rewind`` share; None when the sidecar or the
    signature is absent/unparsable."""
    try:
        with open(os.path.join(tag_dir, "client_state.json")) as f:
            meta = json.load(f)
        return world_device_count(meta.get("world"))
    except (OSError, ValueError, TypeError):
        return None


def annotation_from_worlds(saved_world: Optional[dict],
                           live_world: Optional[dict]) -> Optional[dict]:
    """``{kind, from_world, to_world}`` for a world change between two
    signatures, or None when they describe the same mesh (or either is
    unreadable). THE classification rule every tier prices a resize by —
    the RAM/emergency reshard paths and the disk tier's native
    reshard-on-load must never disagree about what a world change is."""
    from_n = world_device_count(saved_world)
    to_n = world_device_count(live_world)
    if not from_n or not to_n:
        return None
    norm = lambda w: {**w, "mesh_shape": [list(x) for x in
                                          (w.get("mesh_shape") or [])]}
    if norm(saved_world) == norm(live_world):
        return None
    kind = ("shrink" if to_n < from_n
            else "grow" if to_n > from_n else "relayout")
    return {"kind": kind, "from_world": from_n, "to_world": to_n}


# checkpoint-recorded model-layout facts, validated on load. The head-
# grouping fields are the dangerous ones (shape-invariant, silent); the
# size fields ride along for a readable error and cost nothing.
_LAYOUT_FIELDS = ("n_head", "n_kv_head", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "n_embd",
                  "hidden_size", "n_layer")


def model_layout(engine) -> Optional[dict]:
    """Head-layout facts of the engine's model config (``n_head`` and
    siblings), or None when the model carries no config object (bare
    callable losses)."""
    cfg = getattr(getattr(engine, "module", None), "config", None)
    if cfg is None:
        return None
    out = {}
    for f in _LAYOUT_FIELDS:
        v = getattr(cfg, f, None)
        if isinstance(v, int) and not isinstance(v, bool):
            out[f] = v
    return out or None


def check_model_layout(engine, meta: dict, source: str) -> None:
    """Raise :class:`CheckpointLayoutError` when the checkpoint's recorded
    layout disagrees with the live model's on any shared field — naming
    BOTH layouts. Checkpoints predating the record (no ``model_layout``)
    and engines without a config object pass silently."""
    saved = (meta or {}).get("model_layout")
    live = model_layout(engine)
    if not saved or not live:
        return
    diff = {f: (saved[f], live[f]) for f in saved
            if f in live and saved[f] != live[f]}
    if diff:
        raise CheckpointLayoutError(
            f"checkpoint {source} was saved under a different model layout: "
            + "; ".join(f"{f} was {a} at save but is {b} now"
                        for f, (a, b) in sorted(diff.items()))
            + f" (saved layout {saved} vs live {live}). Param shapes are "
            "head-count invariant, so loading would silently reinterpret "
            "the attention grouping — refuse instead. Load with a model "
            "config matching the checkpoint, or re-export the weights "
            "under the new layout.")


def _retry_policy(engine) -> RetryPolicy:
    """The engine's configured retry policy for checkpoint filesystem I/O
    (resilience.retry block); default policy when the engine predates it."""
    res = getattr(getattr(engine, "_config", None), "resilience", None)
    if res is None:
        return RetryPolicy()
    r = res.retry
    if not r.enabled:
        return NO_RETRY
    return RetryPolicy(max_attempts=r.max_attempts, base_delay=r.base_delay,
                       multiplier=r.multiplier, max_delay=r.max_delay,
                       deadline=r.deadline, jitter=r.jitter)


def _flatten_state(state) -> dict:
    """TrainState → flat {path: leaf} dict. Orbax round-trips NamedTuples as
    dicts (losing the type), so we serialize a stable flat layout instead and
    rebuild the typed pytree on load from the engine's live structure."""
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path)
        flat[key] = leaf
    return flat


def _unflatten_like(state, flat: dict):
    paths, treedef = jax.tree_util.tree_flatten_with_path(state)
    leaves = []
    for path, _ in paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path)
        leaves.append(flat[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)


_async_checkpointer = None


def _get_async_checkpointer():
    """Process-wide orbax AsyncCheckpointer (reference nebula/async-tiered
    checkpointing role): device→host copy happens synchronously, the write
    itself in a background thread. Orbax commits via atomic rename, so a
    crash mid-write never leaves a readable-but-corrupt checkpoint."""
    global _async_checkpointer
    if _async_checkpointer is None:
        import orbax.checkpoint as ocp

        _async_checkpointer = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
    return _async_checkpointer


import threading as _threading  # noqa: E402

from deepspeed_tpu.utils import locks as _locks  # noqa: E402

_pending_latest_threads: list = []
_pending_lock = _locks.make_lock("checkpoint.pending")


def register_pending_save(thread) -> None:
    """Track a background save thread (the overlap engine's async
    snapshot commit) so loads / subsequent saves / process exit join it
    exactly like the async-orbax finalize threads."""
    with _pending_lock:
        _pending_latest_threads.append(thread)


def wait_for_pending_saves():
    """Block until any in-flight async checkpoint write commits (and its
    'latest' pointer advance lands). Safe to call FROM a tracked save
    thread (the overlap snapshot commit runs the ordinary save path,
    which starts with this wait): a thread never joins itself — it stays
    registered until a LATER wait drains it, so a concurrent main-thread
    wait always sees (and joins) the in-flight write instead of
    returning early against a half-written tag. List mutation is
    lock-guarded: the main thread and a background commit may wait
    concurrently."""
    if _async_checkpointer is not None:
        _async_checkpointer.wait_until_finished()
    me = _threading.current_thread()
    while True:
        with _pending_lock:
            t = next((x for x in _pending_latest_threads if x is not me),
                     None)
            if t is not None:
                _pending_latest_threads.remove(t)
        if t is None:
            return
        t.join()


# the 'latest'-pointer advance runs on a daemon thread; a trainer that exits
# right after save_checkpoint() must not lose it
import atexit  # noqa: E402

atexit.register(wait_for_pending_saves)


def capture_host_meta(engine) -> dict:
    """The host-side training-progress facts a checkpoint's
    client_state.json records, captured NOW: the async snapshot path
    hands this to its background commit so the metadata describes the
    same instant as the device snapshot — reading the live engine from
    the background thread would pair step-N weights with step-N+k
    LR-schedule/sampler positions (silent wrong-resume)."""
    sampler = getattr(engine, "_data_sampler", None)
    loader = getattr(engine, "dataloader", None)
    return {
        "global_samples": engine.global_samples,
        "micro_steps": engine.micro_steps,
        "lr_scheduler": (engine.lr_scheduler.state_dict()
                         if engine.lr_scheduler is not None else None),
        "data_sampler": sampler.state_dict() if sampler is not None else None,
        # resumable dataloader position (epoch + batch index): replayed
        # steps after a rewind/restore consume the SAME batches —
        # exactly-once sample accounting instead of a silent re-draw
        "data_loader": (loader.state_dict()
                        if loader is not None and hasattr(loader, "state_dict")
                        else None),
    }


def save_engine_checkpoint(engine, save_dir: str, tag: Optional[str] = None,
                           client_state: Optional[dict] = None, save_latest: bool = True,
                           state=None, force_sync: bool = False,
                           host_meta: Optional[dict] = None) -> bool:
    """``state`` overrides the live ``engine.state`` (the overlap engine's
    async snapshot passes its device-side copy — the live tree's buffers
    are donated to the next step and must not be read from a background
    thread); ``host_meta`` (a :func:`capture_host_meta` dict) likewise
    overrides the live host-side progress facts so snapshot metadata is
    consistent with the snapshot; ``force_sync`` bypasses the orbax
    AsyncCheckpointer (the snapshot commit already runs on its own
    thread — nesting a second async layer would just complicate the
    'latest' ordering)."""
    import orbax.checkpoint as ocp

    state = engine.state if state is None else state
    tag = tag or f"global_step{int(state.step)}"
    path = _ckpt_dir(save_dir, tag)
    policy = _retry_policy(engine)
    inj = _chaos.active_injector()

    if jax.process_index() == 0:
        # overwriting an existing tag: its old manifest indexes the PREVIOUS
        # save's bytes, and would invalidate the tag the moment any file is
        # replaced underneath it. Drop it first — until the new manifest
        # lands, a crash degrades to the pre-manifest acceptance (commit
        # marker + parseable client_state) instead of a false corruption.
        # (join any in-flight finalize thread so ITS manifest write cannot
        # land after this drop)
        stale_manifest = os.path.join(path, MANIFEST_NAME)
        wait_for_pending_saves()
        if os.path.exists(stale_manifest):
            def _drop_stale():
                try:
                    os.remove(stale_manifest)
                except FileNotFoundError:
                    pass
            retry(_drop_stale, policy, op="manifest")

    use_async = bool(getattr(engine._config.checkpoint_config, "async_save", False)) \
        and not force_sync
    if use_async:
        ckptr = _get_async_checkpointer()
        ckptr.wait_until_finished()           # one in-flight save at a time
        if inj is not None:
            inj.before("state_save", path)
        ckptr.save(os.path.join(path, "state"), _flatten_state(state), force=True)
    else:
        def _sync_save():
            if _chaos.active_injector() is not None:
                _chaos.active_injector().before("state_save", path)
            with ocp.PyTreeCheckpointer() as c:
                c.save(os.path.join(path, "state"), _flatten_state(state), force=True)

        if jax.process_count() > 1:
            # the orbax save is a cross-host collective: re-running it on ONE
            # host after a local fault would desynchronize the commit barrier
            # while the other hosts have already passed it — fail uniformly
            # and let the launcher restart the whole job
            _sync_save()
        else:
            retry(_sync_save, policy, op="state_save")

    if jax.process_index() == 0:
        # sidecar + metadata payloads are hashed IN MEMORY into the per-tag
        # manifest, so a write that lands corrupt (crash, chaos truncation)
        # fails verification at load time and the restore walks back
        manifest_files = {}
        if host_meta is None:
            host_meta = capture_host_meta(engine)
        sampler_sd = host_meta["data_sampler"]
        if sampler_sd is not None and isinstance(
                sampler_sd.get("admitted"), np.ndarray):
            # the admitted draw order is O(admitted-samples) int64 — sidecar
            # it as .npy (the reference's on-disk data_cluster files role)
            # instead of bloating client_state.json
            buf = io.BytesIO()
            np.save(buf, sampler_sd.pop("admitted"))
            manifest_files["data_sampler_admitted.npy"] = buf.getvalue()
            sampler_sd["admitted_file"] = "data_sampler_admitted.npy"
        meta = {
            "tag": tag,
            "global_steps": int(state.step),
            "skipped_steps": int(state.skipped_steps),
            "global_samples": host_meta["global_samples"],
            "micro_steps": host_meta["micro_steps"],
            "lr_scheduler": host_meta["lr_scheduler"],
            "client_state": client_state or {},
            "zero_stage": engine.zero_stage,
            "dp_world_size": engine.dp_world_size,
            # the placement world + head layout this state was saved
            # under: the resize path prices world changes from the
            # former; the load guard refuses silent attention-grouping
            # reinterpretation from the latter
            "world": world_signature(engine),
            "model_layout": model_layout(engine),
            # curriculum data sampler (reference ds_sampler state in
            # client_sd): rng + draw order + position → mid-epoch resume
            "data_sampler": sampler_sd,
            # dataloader position — the rewind ladder's exactly-once
            # sample accounting rides every tier, including this one
            "data_loader": host_meta.get("data_loader"),
        }
        manifest_files["client_state.json"] = json.dumps(
            meta, default=str).encode("utf-8")

        def _finalize():
            # ordering is the whole point: orbax state has COMMITTED before
            # this runs → sidecars + client_state → manifest (indexes them)
            # → 'latest' pointer last. NOTHING lands in the tag dir before
            # the commit, so a crashed save can never present metadata that
            # makes a state-less tag look restorable; a crash anywhere
            # leaves either the previous tag fully intact or this tag
            # verifiable — never a pointer to a tag that cannot be restored.
            if "data_sampler_admitted.npy" in manifest_files:
                atomic_write_bytes(
                    os.path.join(path, "data_sampler_admitted.npy"),
                    manifest_files["data_sampler_admitted.npy"],
                    op="sampler_sidecar", policy=policy)
            atomic_write_bytes(os.path.join(path, "client_state.json"),
                               manifest_files["client_state.json"],
                               op="client_state", policy=policy)
            write_manifest(path, tag, manifest_files, policy=policy,
                           advance_latest=save_latest)
            if save_latest:
                atomic_write_text(os.path.join(os.path.abspath(save_dir), "latest"),
                                  tag, op="latest", policy=policy)

        if use_async:
            # the manifest and 'latest' pointer must only land AFTER the
            # background write commits (orbax's atomic rename): otherwise a
            # crash mid-write strands a restart on a tag whose state/ never
            # materialized
            def _deferred():
                try:
                    _get_async_checkpointer().wait_until_finished()
                    _finalize()
                except Exception as e:      # daemon thread: surface, don't die silent
                    logger.error(f"async checkpoint {tag}: commit/finalize failed "
                                 f"({e}); 'latest' was not advanced and the tag "
                                 "may not verify")

            t = _locks.spawn_thread(_deferred, name=f"ds-ckpt-finalize-{tag}",
                                    owner="checkpoint", daemon=True)
            t.start()
            register_pending_save(t)    # lock-guarded, unlike a bare append
        else:
            _finalize()
    log_dist(f"saved checkpoint {tag} to {save_dir}", ranks=[0])
    return True


def load_inference_params(load_dir: str, abstract_params: Any,
                          tag: Optional[str] = None) -> Any:
    """Restore ONLY the params subtree of a training checkpoint, directly
    into the SERVING shardings — the TP-reshard serving load (reference
    inference/engine.py:336-506 loads pre-sharded checkpoints / re-slices
    qkv+mlp for the serving mp world; here the reshard is orbax restoring
    into whatever NamedShardings the inference engine computed, so a tp=4
    training checkpoint serves at tp=2 or tp=1 unchanged).

    ``load_dir``: a training save_dir (tag via ``tag`` or its 'latest'
    file), or a tag directory itself. ``abstract_params``: pytree of
    ShapeDtypeStruct carrying the serving shardings (dtype casts apply on
    load). Returns the concrete params pytree.
    """
    wait_for_pending_saves()
    import orbax.checkpoint as ocp

    if os.path.isdir(os.path.join(load_dir, "state")):
        path = os.path.abspath(load_dir)          # a tag dir directly
    else:
        if tag is None:
            latest = os.path.join(os.path.abspath(load_dir), "latest")
            if not os.path.isfile(latest):
                raise FileNotFoundError(
                    f"no 'latest' file in {load_dir}; pass tag= or a tag dir")
            with open(latest) as f:
                tag = f.read().strip()
        path = _ckpt_dir(load_dir, tag)
    if not os.path.isdir(path):
        raise FileNotFoundError(f"checkpoint {path} not found")

    # same key scheme as _flatten_state (which prefixes TrainState fields):
    # the params subtree's keys are exactly "params/<leaf path>"
    flat_abs = {f"params/{k}": v
                for k, v in _flatten_state(abstract_params).items()}
    with ocp.PyTreeCheckpointer() as ckptr:
        restored_flat = ckptr.restore(
            os.path.join(path, "state"), item=dict(flat_abs), transforms={},
            restore_args=ocp.checkpoint_utils.construct_restore_args(flat_abs))
    log_dist(f"loaded serving params from {path}", ranks=[0])
    return _unflatten_like(abstract_params,
                           {k[len("params/"):]: v
                            for k, v in restored_flat.items()})


def apply_restored_meta(engine, meta: dict):
    """Apply a restored checkpoint's host-side progress facts to the live
    engine: sample/step counters, LR schedule, curriculum sampler,
    dataloader position, and the host-step mirror that drives curriculum
    difficulty + logging cadence. Shared by every tier of the restore
    ladder (orbax tags, emergency tags, RAM snapshots)."""
    if meta:
        engine.global_samples = meta.get("global_samples", 0) or 0
        engine.micro_steps = meta.get("micro_steps", 0) or 0
        if engine.lr_scheduler is not None and meta.get("lr_scheduler"):
            engine.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        sampler_sd = meta.get("data_sampler")
        if sampler_sd:
            if getattr(engine, "_data_sampler", None) is not None:
                engine._data_sampler.load_state_dict(sampler_sd)
            else:
                # loader not built yet: deepspeed_io applies it on creation
                engine._pending_sampler_state = sampler_sd
        loader_sd = meta.get("data_loader")
        if loader_sd:
            loader = getattr(engine, "dataloader", None)
            if loader is not None and hasattr(loader, "load_state_dict"):
                try:
                    loader.load_state_dict(loader_sd)
                except ValueError as e:
                    restored = False
                    if getattr(engine, "_elastic_resize", None) is not None:
                        # elasticity.resize: a changed BATCH geometry is a
                        # world resize, not corruption — repartition the
                        # exactly-once position at sample granularity
                        # across the new world (other mismatches still
                        # refuse inside the loader)
                        try:
                            loader.load_state_dict(loader_sd,
                                                   repartition=True)
                            restored = True
                            log_dist(
                                "dataloader position REPARTITIONED across "
                                f"the new batch geometry (captured "
                                f"batch_size="
                                f"{loader_sd.get('batch_size')}, resumed at "
                                f"sample {loader_sd.get('sample_idx', '?')})",
                                ranks=[0])
                        except (TypeError, ValueError) as e2:
                            e = e2
                    if not restored:
                        # a changed dataset/batch geometry: resuming the
                        # old position would mis-account samples — start
                        # the loader fresh and say so
                        logger.warning(
                            f"dataloader position NOT restored ({e}); "
                            "the loader starts from its beginning")
            else:
                logger.warning(
                    "checkpoint carries a dataloader position but this "
                    "engine has no loader to apply it to (pass "
                    "training_data= or set engine.dataloader before "
                    "load_checkpoint for exactly-once sample accounting)")
    # host-side step counter drives curriculum difficulty + logging cadence:
    # resume it from the restored device step, or a resumed run would replay
    # the whole curriculum ramp from min difficulty
    engine._host_step = int(engine.state.step)
    sched = getattr(engine, "curriculum_scheduler", None)
    if sched is not None and getattr(sched, "schedule_type", None) != "custom":
        # custom schedules need the user's fn installed first; train_batch
        # recomputes difficulty from _host_step on the next step anyway
        sched.update_difficulty(engine._host_step + 1)
    pld = getattr(engine, "progressive_layer_drop", None)
    if pld is not None:
        # the jitted step reads θ(t) from the restored state.step; re-sync the
        # host-side reporting mirror so pld_theta() matches it after resume
        pld.update_state(engine._host_step)


def _best_restorable_step(load_dir: str, candidates, verify: bool,
                          cache: dict) -> int:
    """The step of the newest disk candidate that VERIFIES (candidates
    arrive newest-first), -1 when none — what the RAM tier must beat to
    win the ladder. Using an unverified candidate's step here would make
    a corrupt newest tag evict a fresher valid RAM snapshot in favor of
    an older disk checkpoint. Verification verdicts land in ``cache`` so
    the candidate walk never re-hashes a tag."""
    from deepspeed_tpu.resilience.manifest import tag_step

    for cand in candidates:
        if verify:
            verdict = verify_tag(_ckpt_dir(load_dir, cand))
            cache[cand] = verdict
            if not verdict[0]:
                continue
        # an unparsable step (-1) offers no freshness evidence: RAM wins
        return tag_step(cand)
    return -1


def load_engine_checkpoint(engine, load_dir: str, tag: Optional[str] = None,
                           load_optimizer_states: bool = True,
                           load_module_only: bool = False):
    """Verified restore with last-good fallback — the rewind LADDER WALK.

    The freshest VERIFIED tier wins: the tier-0 host-RAM snapshot ring
    (when the engine runs with the ``rewind`` block and the ring holds a
    snapshot at least as new as the best disk candidate), then the disk
    candidates newest-first — tier-1 ``emergency_step<N>`` tags restored
    from their npz payload, tier-2 orbax tags as before. Each candidate
    must pass the manifest check (``resilience.verify_on_load``) and then
    actually restore — orbax exceptions, corrupt metadata, and emergency
    snapshots whose world signature no longer matches all demote to the
    next candidate rather than stranding the run. The 'latest' pointer is
    a hint, not an authority: a tag whose save died between the state
    commit and the pointer advance — or an emergency tag that never
    advanced it — is still found and restored. Every successful restore
    stamps ``engine._last_recovery = {tier, snapshot_step, steps_lost,
    restore_s}``.
    """
    wait_for_pending_saves()              # an async save may still be writing
    if jax.process_count() > 1:
        # the manifest and 'latest' land from PROCESS 0's finalize; every
        # other host's wait above returns at once (it has no finalize of its
        # own) and would verify the tag before they exist, skip it, and
        # leave process 0 alone in the collective restore
        from deepspeed_tpu.comm import comm as _comm

        _comm.barrier(log_name="ds_ckpt_load")
    import time as _time

    import orbax.checkpoint as ocp

    engine._last_recovery = None
    res = getattr(getattr(engine, "_config", None), "resilience", None)
    verify = res.verify_on_load if res is not None else True
    fallback = res.fallback_to_last_good if res is not None else True
    rewind_mgr = getattr(engine, "_rewind", None)

    # the 'latest' pointer is a hint that candidate_tags deliberately
    # outranks with any newer committed auto-resume tag
    # (crash-between-commit-and-advance)
    candidates = candidate_tags(load_dir, preferred=tag)

    # ---- tier-0: the host-RAM snapshot ring (rewind block only) ----------
    # an explicit tag is a contract (see below) — the RAM tier never
    # substitutes for it. Partial loads (load_module_only / no optimizer
    # states) are explicit "weights from THAT source" requests the full
    # in-RAM training state must not hijack, and a snapshot captured
    # under a different checkpoint dir never serves a load pointed
    # elsewhere (restore_from_ram's for_dir affinity). Otherwise the
    # freshest verified tier wins.
    verified_cache: dict = {}
    if rewind_mgr is not None and tag is None and not load_module_only \
            and load_optimizer_states:
        info = rewind_mgr.restore_from_ram(
            min_step=_best_restorable_step(load_dir, candidates, verify,
                                           verified_cache),
            for_dir=load_dir)
        if info is not None:
            return f"ram://step{info['snapshot_step']}", {}

    if tag is not None:
        # an explicit tag is a contract: restoring a DIFFERENT checkpoint
        # than the one asked for would be silent wrong-weights corruption —
        # fail instead of falling back
        if tag not in candidates:
            logger.warning(f"checkpoint {_ckpt_dir(load_dir, tag)} not found")
            return None, {}
        candidates = [tag]
    if not candidates:
        logger.warning(f"no checkpoint tags in {load_dir}; nothing loaded")
        return None, {}
    if not fallback:
        candidates = candidates[:1]

    # Restore directly into the engine's current shardings (reshard-on-load).
    abstract = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        engine.state, engine.state_shardings)
    skipped = []
    tier = "disk"
    t_restore = _time.perf_counter()
    for cand in candidates:
        path = _ckpt_dir(load_dir, cand)
        if verify:
            cached = verified_cache.get(cand)
            ok, reason = cached if cached is not None else verify_tag(path)
            if not ok:
                logger.warning(f"skipping checkpoint {cand!r}: {reason}")
                skipped.append(cand)
                continue
        is_emergency = is_emergency_tag(path)
        if is_emergency and rewind_mgr is None:
            # the strict no-op contract keeps the rewind module unloaded
            # without its block — an emergency tag is then explicitly
            # (loudly) not a candidate, never a half-understood one
            logger.warning(
                f"skipping emergency snapshot tag {cand!r}: the 'rewind' "
                "ds_config block is absent (enable it to restore "
                "preemption emergency saves)")
            skipped.append(cand)
            continue
        try:
            if is_emergency:
                restored, meta = rewind_mgr.load_emergency_tag(path)
                if restored is None:    # world mismatch — warned inside
                    skipped.append(cand)
                    continue
                tier = "emergency"
            else:
                with ocp.PyTreeCheckpointer() as ckptr:
                    restored_flat = ckptr.restore(
                        os.path.join(path, "state"),
                        restore_args=ocp.checkpoint_utils.construct_restore_args(_flatten_state(abstract)))
                restored = _unflatten_like(engine.state, restored_flat)
                meta = {}
                meta_path = os.path.join(path, "client_state.json")
                if os.path.isfile(meta_path):
                    with open(meta_path) as f:
                        meta = json.load(f)
                tier = "disk"
            # the curriculum sampler's admitted order rides a sidecar on
            # BOTH tiers (json would corrupt the int64 array)
            sampler_sd = meta.get("data_sampler")
            if sampler_sd and sampler_sd.get("admitted_file"):
                sampler_sd["admitted"] = np.load(
                    os.path.join(path, sampler_sd.pop("admitted_file")))
        except Exception as e:
            from deepspeed_tpu.elasticity.config import ElasticityError
            if isinstance(e, ElasticityError):
                # a resize POLICY violation (min_world_size) is a loud
                # refusal, never a demotion: every candidate would land
                # on the same forbidden world
                raise
            # half-written orbax dirs, unparseable JSON, truncated sidecars:
            # everything restore-side demotes to the next-newest candidate
            logger.warning(f"skipping checkpoint {cand!r}: restore failed ({e})")
            skipped.append(cand)
            continue
        break
    else:
        if rewind_mgr is not None and tag is None and not load_module_only \
                and load_optimizer_states:
            # the disk tiers all failed: a RAM snapshot OLDER than the
            # best (unrestorable) disk step is still infinitely better
            # than nothing — walk the ring again without the freshness
            # gate (dir affinity still applies)
            info = rewind_mgr.restore_from_ram(for_dir=load_dir)
            if info is not None:
                logger.warning(
                    f"no restorable disk checkpoint in {load_dir} (tried "
                    f"{candidates}); recovered from the RAM tier @step "
                    f"{info['snapshot_step']}")
                return f"ram://step{info['snapshot_step']}", {}
        logger.warning(f"no restorable checkpoint in {load_dir} "
                       f"(tried {candidates}); nothing loaded")
        return None, {}

    # head-layout guard BEFORE any state is applied; deliberately outside
    # the demotion loop — every candidate of this run shares the layout,
    # so walking back would repeat the mismatch against an older step
    check_model_layout(engine, meta, source=os.path.basename(str(cand)))

    # world change = a RESIZE served by this tier (the disk tier reshards
    # natively via orbax; the RAM/emergency tiers resharded above when
    # elasticity.resize armed them) — priced into the recovery record
    resize_info = None
    saved_world = (meta or {}).get("world")
    if saved_world is not None:
        resize_info = annotation_from_worlds(saved_world,
                                             world_signature(engine))
    rz_cfg = getattr(engine, "_elastic_resize", None)
    if resize_info is not None and rz_cfg is not None:
        from deepspeed_tpu.elasticity import resize as _resize

        # min_world_size raises LOUDLY inside; a tiers exclusion reaching
        # THIS tier also raises — it is the bottom of the ladder, there
        # is no deeper tier left to demote to
        if not _resize.check_resize_allowed(rz_cfg, resize_info, tier=tier):
            raise _resize.ResizeError(
                f"resize {resize_info['kind']} {resize_info['from_world']}"
                f" -> {resize_info['to_world']} device(s) would be served "
                f"by the {tier!r} tier, which elasticity.resize.tiers="
                f"{list(rz_cfg.tiers)} excludes — and no deeper tier can "
                "serve it")

    if load_module_only or not load_optimizer_states:
        state = engine.state._replace(params=restored.params,
                                      master=restored.master if not load_module_only else engine.state.master)
    else:
        state = restored
    engine.state = state

    apply_restored_meta(engine, meta)
    rew_meta = (meta or {}).get("rewind") or {}
    engine._last_recovery = {
        "tier": tier,
        "snapshot_step": int(engine.state.step),
        # an emergency tag knows at save time how many steps it is behind
        # the stop boundary; orbax tags leave it to the caller (the agent
        # diffs against the failing step)
        "steps_lost": rew_meta.get("steps_lost_at_save"),
        "restore_s": round(_time.perf_counter() - t_restore, 4),
    }
    if resize_info is not None:
        engine._last_recovery["resize"] = resize_info
        engine._last_recovery["reshard_s"] = \
            engine._last_recovery["restore_s"]
        if rz_cfg is not None:
            from deepspeed_tpu.elasticity import resize as _resize

            _resize.note_resize_event(
                resize_info, tier=tier,
                reshard_s=engine._last_recovery["reshard_s"])
    if rewind_mgr is not None:
        rewind_mgr.note_recovery(engine._last_recovery)
    if skipped:
        log_dist(f"checkpoint fallback: restored {cand!r} after skipping "
                 f"{skipped} (corrupt/unverified)", ranks=[0])
    log_dist(f"loaded checkpoint {cand} from {load_dir}", ranks=[0])
    return path, meta.get("client_state", {})
