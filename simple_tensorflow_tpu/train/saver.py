"""Saver: checkpoint save/restore
(ref: tensorflow/python/training/saver.py, core/util/tensor_bundle/ — the
reference's TensorBundle shards tensors into data files + index).

TPU-native checkpoint format ("stf-bundle"): one ``<prefix>.stfz`` npz
holding all tensors (fetched from the device-resident VariableStore) plus a
``<prefix>.index.json`` with dtypes/shapes/shardings, and the classic
``checkpoint`` state file for latest_checkpoint/max_to_keep compatibility.
An orbax backend (async, multi-host, sharded arrays) is available via
``Saver(..., backend="orbax")`` for pod-scale jobs.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, List, Optional

import numpy as np

from ..framework import graph as ops_mod
from ..framework import errors
from ..ops import variables as variables_mod


class CheckpointState:
    def __init__(self, model_checkpoint_path="", all_model_checkpoint_paths=None):
        self.model_checkpoint_path = model_checkpoint_path
        self.all_model_checkpoint_paths = all_model_checkpoint_paths or []


def _state_path(directory, latest_filename=None):
    return os.path.join(directory, latest_filename or "checkpoint")


def update_checkpoint_state(save_dir, model_checkpoint_path,
                            all_model_checkpoint_paths=None,
                            latest_filename=None):
    """(ref: python/training/saver.py ``update_checkpoint_state``).
    Committed through the atomic temp+fsync+``os.replace`` protocol
    (stf.checkpoint.atomic): the state file is the pointer that makes a
    checkpoint "latest", so a crash mid-update must leave the previous
    pointer intact, never a truncated JSON."""
    from ..checkpoint import atomic as atomic_io

    state = {
        "model_checkpoint_path": model_checkpoint_path,
        "all_model_checkpoint_paths": all_model_checkpoint_paths or
        [model_checkpoint_path],
    }
    atomic_io.atomic_write_json(_state_path(save_dir, latest_filename),
                                state, label="state")


def get_checkpoint_state(checkpoint_dir, latest_filename=None):
    path = _state_path(checkpoint_dir, latest_filename)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return CheckpointState(d.get("model_checkpoint_path", ""),
                           d.get("all_model_checkpoint_paths", []))


def latest_checkpoint(checkpoint_dir, latest_filename=None):
    """(ref: saver.py:1612 ``latest_checkpoint``)."""
    st = get_checkpoint_state(checkpoint_dir, latest_filename)
    if st and st.model_checkpoint_path:
        if checkpoint_exists(st.model_checkpoint_path):
            return st.model_checkpoint_path
    return None


def checkpoint_exists(checkpoint_prefix):
    return (os.path.exists(checkpoint_prefix + ".stfz") or
            os.path.isdir(checkpoint_prefix + ".orbax"))


def load_checkpoint_values(checkpoint_prefix):
    """{variable_name: ndarray} from an stf-bundle checkpoint — the ONE
    place that knows npz keys are '/'-flattened with '|' (the save path
    below writes them that way). Tools (freeze_graph, inspect_checkpoint)
    read through this."""
    import numpy as np

    prefix = (checkpoint_prefix[:-len(".stfz")]
              if checkpoint_prefix.endswith(".stfz")
              else checkpoint_prefix)
    sharded_meta = {}
    try:
        with open(prefix + ".index.json") as f:
            for key, meta in json.load(f).get("tensors", {}).items():
                if meta.get("sharded_layout"):
                    sharded_meta[key] = meta
    except (OSError, json.JSONDecodeError, KeyError):
        pass  # no/old index: every npz entry is a whole tensor
    with np.load(prefix + ".stfz", allow_pickle=False) as data:
        from ..checkpoint import snapshot as snapshot_mod

        out = {}
        shard_keys = {s["key"] for m in sharded_meta.values()
                      for s in m["sharded_layout"]["shards"]}
        for k in data.files:
            logical = k.replace("|", "/")
            if logical not in shard_keys:
                out[logical] = data[k]
        for key, meta in sharded_meta.items():
            out[key] = snapshot_mod.assemble_sharded(data, meta)
        return out


def _capture_host_state(sess):
    """Session RNG position + data-iterator positions (SURVEY §5: resume
    restores global_step, optimizer slots, RNG key, data-pipeline epoch).
    One implementation, on the session (the async checkpoint plane
    captures it at the same barrier as the device snapshot)."""
    return sess.snapshot_host_state()


def resolve_global_step(sess, global_step):
    """The integer step a checkpoint prefix is suffixed with: an int
    passes through, a Variable/Tensor is read (straight from the device
    store when possible — no Session.run dispatch), None stays None."""
    if global_step is None:
        return None
    if isinstance(global_step, (int, np.integer)):
        return int(global_step)
    try:
        target = global_step._ref if hasattr(global_step, "_ref") \
            else global_step
        return int(np.asarray(sess.variable_value(target)))
    except (KeyError, AttributeError):
        pass
    if hasattr(global_step, "_ref") or isinstance(global_step,
                                                  ops_mod.Tensor):
        return int(np.asarray(sess.run(
            global_step._ref if hasattr(global_step, "_ref")
            else global_step)))
    return int(global_step)


def _iter_ordinal(name):
    """Creation ordinal of an auto-named iterator ('dataset_iterator_7'
    -> 7); unparseable names sort last, stably."""
    tail = name.rsplit("_", 1)[-1]
    return (0, int(tail)) if tail.isdigit() else (1, 0)


def _restore_host_state(sess, host_state):
    if not host_state:
        return  # pre-round-2 checkpoint: nothing recorded
    if "rng_run_counter" in host_state:
        sess._run_counter = int(host_state["rng_run_counter"])
    iterators = host_state.get("iterators") or {}
    if iterators:
        from ..data import dataset as dataset_mod

        reg = dataset_mod.iterator_registry(sess.graph)
        mapping = {}
        if any(n not in reg for n in iterators) and \
                len(iterators) == len(reg):
            # iterator auto-names ride a PROCESS-global counter, so an
            # in-process graph rebuild (or any program that built other
            # iterators first) shifts every name and exact lookup finds
            # nothing — silently resuming every pipeline from element 0.
            # Both sides created their iterators in program order, so
            # when the counts match, align by creation order instead.
            saved = sorted(iterators, key=_iter_ordinal)
            live = sorted(reg, key=_iter_ordinal)
            mapping = dict(zip(saved, live))
            if any(s != l for s, l in mapping.items()):
                from ..platform import tf_logging as logging

                logging.info(
                    "Saver.restore: aligning %d data iterator(s) by "
                    "creation order (checkpoint names %s -> live names "
                    "%s)", len(mapping), saved, live)
        for name, st in iterators.items():
            # when order-alignment is active it is used EXCLUSIVELY: on
            # partial name overlap a mix of exact and mapped lookups
            # would pair one live iterator with two saved states and
            # leave another with none
            it = reg.get(mapping[name]) if mapping else reg.get(name)
            if it is not None:
                it.restore_state(st)


class Saver:
    """(ref: python/training/saver.py:1040 ``class Saver``)."""

    def __init__(self, var_list=None, reshape=False, sharded=False,
                 max_to_keep=5, keep_checkpoint_every_n_hours=10000.0,
                 name=None, restore_sequentially=False, saver_def=None,
                 builder=None, defer_build=False, allow_empty=False,
                 write_version=2, pad_step_number=False, backend="native"):
        self._var_list = var_list
        self._max_to_keep = max_to_keep
        self._keep_every_s = keep_checkpoint_every_n_hours * 3600.0
        if backend not in ("native", "orbax", "async"):
            raise ValueError(
                f"Unknown Saver backend {backend!r}; use 'native' (single "
                "npz bundle), 'async' (native format, barrier snapshot + "
                "background stf_ckpt_writer commit — stf.checkpoint), or "
                "'orbax' (sharded, multi-host, no host gather)")
        self._backend = backend
        # backend="async": save() delegates to the stf.checkpoint plane
        # (same on-disk format; restore is identical). Lazy — the engine
        # binds this Saver's var set and retention bookkeeping.
        self._async_engine = None
        # (prefix, save_time) pairs — keep_checkpoint_every_n_hours decides
        # on the CHECKPOINT's timestamp, matching ref saver.py semantics
        self._last_checkpoints: List[tuple] = []
        self._next_keep_time = time.time() + self._keep_every_s
        g = ops_mod.get_default_graph()
        g.add_to_collection(ops_mod.GraphKeys.SAVERS, self)

    def _vars(self) -> Dict[str, "variables_mod.Variable"]:
        vl = self._var_list
        if vl is None:
            vl = (variables_mod.global_variables() +
                  ops_mod.get_default_graph().get_collection(
                      ops_mod.GraphKeys.SAVEABLE_OBJECTS))
        if isinstance(vl, dict):
            return {k: v for k, v in vl.items()}
        out = {}
        for v in vl:
            key = v.var_name if hasattr(v, "var_name") else v.name
            out[key] = v
        return out

    # -- save ----------------------------------------------------------------
    def save(self, sess, save_path, global_step=None, latest_filename=None,
             meta_graph_suffix="meta", write_meta_graph=True,
             write_state=True):
        """(ref: saver.py:1453 ``Saver.save``). ``backend="async"``
        returns as soon as the barrier snapshot is captured; the
        stf_ckpt_writer thread commits in the background
        (``stf.checkpoint``, docs/CHECKPOINT.md)."""
        if self._backend == "async":
            if self._async_engine is None:
                from ..checkpoint.manager import AsyncSaverEngine

                self._async_engine = AsyncSaverEngine(self)
            return self._async_engine.save(
                sess, save_path, global_step=global_step,
                latest_filename=latest_filename,
                write_meta_graph=write_meta_graph,
                write_state=write_state)
        t0 = time.perf_counter()
        step_val = resolve_global_step(sess, global_step)
        prefix = f"{save_path}-{step_val}" if step_val is not None \
            else save_path
        os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)

        vars_map = self._vars()
        store = sess._variable_store
        from ..checkpoint import snapshot as snapshot_mod

        index = {}
        device_state = {}
        for key, v in vars_map.items():
            name = v.var_name if hasattr(v, "var_name") else key
            if name not in store.values:
                raise errors.FailedPreconditionError(
                    None, None, f"Variable {name} is uninitialized; cannot save.")
            arr = store.values[name]
            device_state[key] = arr
            index[key] = {"dtype": str(arr.dtype), "shape": list(arr.shape),
                          "store_name": name,
                          "sharding": snapshot_mod.sharding_desc(arr)}

        host_state = _capture_host_state(sess)
        if self._backend == "orbax":
            self._save_orbax(prefix, device_state)
            from ..checkpoint import atomic as atomic_io

            atomic_io.atomic_write_json(
                prefix + ".index.json",
                snapshot_mod.build_index_doc(index, host_state, "orbax"),
                label="index")
        else:
            # blocking native path, same serialize+atomic-commit
            # pipeline as the async writer: npz bytes -> checksum in the
            # index -> temp+fsync+replace for data then index. Device-
            # sharded arrays pass through ungathered — the flatten step
            # inside write_native_checkpoint D2H's them one shard at a
            # time into flat `key@shard<i>of<n>` entries (ISSUE 19:
            # per-shard embedding-table saves)
            arrays = {}
            for key in device_state:
                arr = device_state[key]
                arrays[key] = arr \
                    if snapshot_mod.shard_split(arr) is not None \
                    else store.as_numpy(index[key]["store_name"])
            snapshot_mod.write_native_checkpoint(prefix, arrays, index,
                                                 host_state)
        if write_meta_graph:
            try:
                from ..framework import graph_io

                graph_io.export_meta_graph(prefix + ".meta",
                                           graph=sess.graph)
            except Exception as e:  # noqa: BLE001
                from ..platform import tf_logging as logging

                logging.warning(
                    "Saver: meta-graph export to %s.meta failed (%s); "
                    "checkpoint tensors were saved.", prefix, e)
        self._manage_old(prefix)
        if write_state:
            update_checkpoint_state(os.path.dirname(prefix) or ".", prefix,
                                    [p for p, _ in self._last_checkpoints],
                                    latest_filename)
        from ..checkpoint import metrics as ckpt_metrics

        ckpt_metrics.saves.get_cell("blocking").increase_by(1)
        ckpt_metrics.save_stall_seconds.get_cell("blocking").add(
            time.perf_counter() - t0)
        return prefix

    def _save_orbax(self, prefix, device_state):
        """Sharded save: each device/host writes its own array shards via
        orbax (OCDBT) — no full-array gather to host numpy, which is what
        makes pod-scale checkpoints feasible (ref tensor_bundle sharding,
        core/util/tensor_bundle/). Keys are flattened ('/' in variable
        names is preserved by a dict tree)."""
        import orbax.checkpoint as ocp

        path = os.path.abspath(prefix + ".orbax")
        if os.path.isdir(path):
            import shutil

            shutil.rmtree(path)  # re-save over same step
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(path, dict(device_state))
            ckptr.wait_until_finished()

    def _restore_orbax(self, sess, save_path, vars_map, index):
        import jax
        import orbax.checkpoint as ocp

        store = sess._variable_store
        path = os.path.abspath(save_path + ".orbax")
        # Abstract target: restore straight into each variable's declared
        # sharding — orbax reads only the local shards per device.
        abstract = {}
        for key, v in vars_map.items():
            meta = index.get(key)
            if meta is None:
                raise errors.NotFoundError(
                    None, None,
                    f"Key {key} not found in checkpoint {save_path}")
            name = meta["store_name"]
            sharding = store.shardings.get(name)
            if sharding is None and name in store.values:
                sharding = store.values[name].sharding
            if sharding is not None:
                abstract[key] = jax.ShapeDtypeStruct(
                    tuple(meta["shape"]), np.dtype(meta["dtype"]),
                    sharding=sharding)
            else:
                abstract[key] = jax.ShapeDtypeStruct(
                    tuple(meta["shape"]), np.dtype(meta["dtype"]))
        with ocp.StandardCheckpointer() as ckptr:
            restored = ckptr.restore(path, abstract)
        for key, v in vars_map.items():
            name = index[key]["store_name"]
            store.values[name] = restored[key]

    def _manage_old(self, new_prefix):
        self._last_checkpoints.append((new_prefix, time.time()))
        while (self._max_to_keep and
               len(self._last_checkpoints) > self._max_to_keep):
            old, saved_at = self._last_checkpoints.pop(0)
            if saved_at > self._next_keep_time:
                # ref semantics (saver.py _MaybeDeleteOldCheckpoints): the
                # keep-forever decision is based on the checkpoint's OWN
                # save time crossing the keep interval boundary, and the
                # boundary advances by one interval
                self._next_keep_time += self._keep_every_s
                continue  # keep this one forever
            for suffix in (".stfz", ".index.json", ".meta"):
                try:
                    os.remove(old + suffix)
                except OSError:
                    pass
            if os.path.isdir(old + ".orbax"):
                import shutil

                shutil.rmtree(old + ".orbax", ignore_errors=True)
            from ..checkpoint import metrics as ckpt_metrics

            ckpt_metrics.gc_deleted.get_cell().increase_by(1)

    # -- restore -------------------------------------------------------------
    def restore(self, sess, save_path, verify_checksum=True):
        """(ref: saver.py:1560 ``Saver.restore``). Loads arrays straight into
        the device-resident store (with the variable's sharding when on a
        mesh) — no restore ops to run. Also restores host state (session RNG
        position, data-iterator positions) so a resumed run reproduces the
        same dropout masks and batch stream (SURVEY §5). Checkpoints
        carrying a content checksum (index v2, stf.checkpoint commit
        protocol) are verified against it — a corrupted bundle raises
        DataLossError instead of loading garbage weights.
        ``verify_checksum=False`` skips that pass (and its full
        read-into-memory) for callers that just verified the file, e.g.
        ``CheckpointManager.restore``."""
        if not checkpoint_exists(save_path):
            raise errors.NotFoundError(
                None, None, f"Checkpoint {save_path} not found")
        with open(save_path + ".index.json") as f:
            idx_doc = json.load(f)
        index = idx_doc["tensors"]
        vars_map = self._vars()
        if os.path.isdir(save_path + ".orbax"):
            self._restore_orbax(sess, save_path, vars_map, index)
        else:
            expected = idx_doc.get("checksum") if verify_checksum \
                else None
            if expected is not None:
                import io

                from ..checkpoint import atomic as atomic_io
                from ..checkpoint import metrics as ckpt_metrics

                with open(save_path + ".stfz", "rb") as f:
                    payload = f.read()
                actual = atomic_io.checksum_bytes(payload)
                if actual != expected:
                    ckpt_metrics.integrity_failures.get_cell(
                        "checksum_mismatch").increase_by(1)
                    raise errors.DataLossError(
                        None, None,
                        f"Checkpoint {save_path}.stfz is corrupt: "
                        f"checksum {actual} != recorded {expected}")
                source = io.BytesIO(payload)
            else:
                source = save_path + ".stfz"
            from ..checkpoint import snapshot as snapshot_mod

            with np.load(source, allow_pickle=False) as data:
                for key, v in vars_map.items():
                    safe = key.replace("/", "|")
                    meta = index.get(key) or {}
                    if safe in data:
                        value = data[safe]
                    elif meta.get("sharded_layout"):
                        # flat per-shard save: reassemble the logical
                        # tensor; store.load re-applies the live
                        # sharding on the way back to device
                        value = snapshot_mod.assemble_sharded(data, meta)
                    else:
                        raise errors.NotFoundError(
                            None, None,
                            f"Key {key} not found in checkpoint {save_path}")
                    if value.dtype.kind == "V" and meta.get("dtype"):
                        # npz keeps the bytes of a dtype NumPy does not
                        # know natively (bfloat16) but reads them back
                        # as void: the index recorded the real dtype
                        import jax.numpy as jnp

                        value = value.view(jnp.dtype(meta["dtype"]))
                    name = v.var_name if hasattr(v, "var_name") else key
                    sess._variable_store.load(name, value, v
                                              if hasattr(v, "dtype") else None)
        _restore_host_state(sess, idx_doc.get("host_state"))

    @property
    def last_checkpoints(self):
        return [p for p, _ in self._last_checkpoints]

    def set_last_checkpoints_with_time(self, pairs):
        self._last_checkpoints = [(p, t) for p, t in pairs]

    def recover_last_checkpoints(self, checkpoint_paths):
        self._last_checkpoints = [(p, time.time())
                                  for p in checkpoint_paths
                                  if checkpoint_exists(p)]

    def wait_until_finished(self, timeout=None):
        """Block until every async save this Saver enqueued has
        committed (no-op for blocking backends); re-raises the first
        background failure."""
        if self._async_engine is not None:
            self._async_engine.wait_until_finished(timeout)

    def as_saver_def(self):
        return {"format": "stf-bundle-v1"}

    def to_proto(self, export_scope=None):
        return self.as_saver_def()

    @staticmethod
    def from_proto(saver_def, import_scope=None):
        return Saver()


def import_meta_graph(meta_graph_or_file, clear_devices=False,
                      import_scope=None, **kwargs):
    from ..framework import graph_io

    graph_io.import_meta_graph(meta_graph_or_file)
    return Saver()


def export_meta_graph(filename=None, meta_info_def=None, graph_def=None,
                      saver_def=None, collection_list=None, as_text=False,
                      graph=None, **kwargs):
    from ..framework import graph_io

    return graph_io.export_meta_graph(filename, graph=graph)
