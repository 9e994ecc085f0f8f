"""Classifier engine driver.

Business API parity with the reference's classifier service
(jubatus/server/server/classifier.idl: train / classify / get_labels /
set_label / delete_label / clear; server logic classifier_serv.cpp:90-146):

- unseen labels are auto-registered on train
- get_labels returns {label: trained_count}
- classify returns per-datum (label, score) for every live label

TPU design: labels are rows of dense [L, D] arrays (ops/classifier.py);
the vocabulary is host metadata. Before a mix, replicas align vocabularies
via sync_schema (sorted union + row permutation) so array diffs psum exactly
(parallel/mix.py). Label train-counts ride the same diff as a dense [L]
array.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jubatus_tpu.core.datum import Datum
from jubatus_tpu.core.fv import make_fv_converter
from jubatus_tpu.core.sparse import _bucket
from jubatus_tpu.framework.driver import DriverBase, locked
from jubatus_tpu.models.classifier_nn import NN_METHODS as _NN_METHODS
from jubatus_tpu.ops import classifier as ops

_LINEAR_METHODS = set(ops.METHODS)
_INITIAL_CAPACITY = 8

# A train flush of uneven rows is handed to the chip as slabs: a row of n
# entries becomes ceil(n / _SLAB_WIDTH) rows of _SLAB_WIDTH entries, their
# count bucketed to a power of two, where that issues at most 1 /
# _SLAB_GAIN of the entries the rows would (gather and scatter cost per
# entry issued, padding included). Swept on v5e at D = 2^23 and 32 label
# rows over flushes of 8,000 documents of 89.8 features at 8,192 x 1,024
# (PERF.md section 6, PR 35; docs/PERF_NOTES.md has the table): rows
# 1,625 ms a flush; slabs of 32, 64, 128, 256 in their power of two 191,
# 178, 348, 710; on the width ladder's rungs less, but a flush's slab
# count straddles a rung (two programs in a window). Rows and slabs cross
# where the slabs issue 1 / 1.1 of the rows' entries; full rows cannot
# reach 1 / 2 whatever the two buckets' chance.
_SLAB_WIDTH = 64
_SLAB_GAIN = 2


class ClassifierConfigError(ValueError):
    pass


class ClassifierDriver(DriverBase):
    TYPE = "classifier"

    def __init__(self, config: dict, dim_bits: int = 18,
                 train_mode: str = "parallel", mesh=None,
                 mesh_axis: str = "shard", shard_features: int = 0):
        super().__init__()
        self.config = config
        self.config_json = json.dumps(config)
        # "parallel" = vectorized microbatch (TPU hot path); "sequential" =
        # exact per-datum reference semantics (ops/classifier.py).
        self.train_mode = train_mode
        # mesh: shard the feature dimension of every [L, D] table over the
        # mesh axis — ONE server exploits all its local chips. The hot
        # train/classify paths run as shard_map programs
        # (parallel/sharded_model.py): a train flush is routed by column
        # range on the host and each chip is uploaded its own entries, a
        # query masks the batch to the owning shard, one psum reduces the
        # [B, L] logits, and the weight matrix is never gathered. The dense
        # uniform-schema plan keeps GSPMD partitioning of the placed state.
        # Orthogonal to cross-server data parallelism via the mix plane
        # (parallel/spmd.py stacks both for the pod path).
        method = config.get("method")
        if method in _NN_METHODS:
            # instance-based classifier over the NN engine — separate driver
            # path, built with ops/knn (models/classifier_nn.py when present).
            raise NotImplementedError(
                f"NN-based classifier method {method!r} handled by "
                "ClassifierNNDriver"
            )
        if method not in _LINEAR_METHODS:
            raise ClassifierConfigError(f"unknown classifier method {method!r}")
        self.method = method
        param = config.get("parameter") or {}
        self.param = float(param.get("regularization_weight", 1.0))
        self.converter = make_fv_converter(config.get("converter"), dim_bits=dim_bits)
        # --shard-features D_PER_SHARD: derive the shard count from the
        # per-device feature budget (the HBM-capacity lever)
        if shard_features and mesh is None:
            from jubatus_tpu.parallel.sharded_model import mesh_for_features

            mesh = mesh_for_features(self.converter.dim, shard_features,
                                     ClassifierConfigError)
        self._mesh = mesh
        self._mesh_axis = mesh_axis
        # sharding derives from the converter's dim, not the dim_bits
        # argument — a config-side "hash_max_size" overrides the latter
        self._sharding = None
        if mesh is not None:
            from jubatus_tpu.native import ingest
            from jubatus_tpu.parallel.mesh import make_feature_sharding
            from jubatus_tpu.parallel.sharded_model import flush_sharding

            self._sharding = make_feature_sharding(
                mesh, mesh_axis, self.converter.hasher.dim_bits,
                ClassifierConfigError, rank=2)
            # a routed train flush: shard n's [Ks, B] plane on chip n
            # (parallel/sharded_model.py route_rows), at a width that
            # only grows among flushes of one width K: {K: Ks}
            self._flush_sharding = flush_sharding(mesh, mesh_axis)
            self._shard_width: Dict[int, int] = {}
            # the routing's native library is built or loaded here, not
            # under the driver's lock at the first flush
            ingest.available()
        self._confidence = method in ops.CONFIDENCE_METHODS
        self.state = None
        self._init_model()

    def _leaf_sharding(self, shape):
        """The mesh's feature sharding for an [L, D] leaf; None for the
        (1, 1) placeholders, and without a mesh."""
        if (self._sharding is not None and len(shape) == 2
                and shape[1] == self.converter.dim):
            return self._sharding
        return None

    def _place(self, state: ops.ClassifierState) -> ops.ClassifierState:
        """Pin [L, D] leaves to the feature-sharded layout (no-op without
        a mesh; (1,1) placeholders stay replicated)."""
        if self._sharding is None:
            return state
        return ops.ClassifierState(*(
            jax.device_put(leaf, self._leaf_sharding(leaf.shape))
            for leaf in state))

    def _let_go(self) -> None:
        """Drop the state there is before a new one is made: two do not
        fit where one fills over half a chip (8.59e9 B at D = 2^28 over
        four). Queued steps still hold the old buffers, so wait for them
        first: the release is then immediate."""
        jax.block_until_ready(self.state)
        self.state = None

    def _init_model(self) -> None:
        self.labels: List[str] = []           # slot -> label name
        self.label_slots: Dict[str, int] = {}  # label name -> slot
        self.capacity = _INITIAL_CAPACITY
        self._let_go()
        # born in its layout: on a mesh no [L, D] leaf ever lies whole on
        # one device
        self.state = ops.init_state(self.capacity, self.converter.dim,
                                    self._confidence, self._sharding)
        self.label_counts = np.zeros(self.capacity, dtype=np.float32)
        self._dcounts = np.zeros(self.capacity, dtype=np.float32)
        self._label_gauges()

    def _label_gauges(self) -> None:
        """Live labels beside the rows the tables reserve for them."""
        if self.trace is not None:
            self.trace.gauge("model.labels_live", len(self.label_slots))
            self.trace.gauge("model.label_capacity", self.capacity)

    # -- label management ----------------------------------------------------
    def _mask(self) -> jnp.ndarray:
        m = np.zeros(self.capacity, dtype=bool)
        for s in self.label_slots.values():
            m[s] = True
        return jnp.asarray(m)

    def _ensure_label(self, label: str) -> int:
        slot = self.label_slots.get(label)
        if slot is not None:
            return slot
        # reuse a freed slot if any, else grow capacity
        used = set(self.label_slots.values())
        free = [s for s in range(self.capacity) if s not in used]
        if free:
            slot = free[0]
        else:
            # every table is copied into one of twice the rows, under the
            # caller's lock; the old state lives until the copy has run
            with self._span("model.grow_labels"):
                self.capacity *= 2
                self.state = ops.grow_labels(self.state, self.capacity,
                                             self._sharding)
            self.label_counts = np.pad(self.label_counts, (0, self.capacity // 2))
            self._dcounts = np.pad(self._dcounts, (0, self.capacity // 2))
            slot = len(self.labels)
            if self.trace is not None:
                self.trace.count("model.label_grow")
        if slot == len(self.labels):
            self.labels.append(label)
        else:
            self.labels[slot] = label
        self.label_slots[label] = slot
        self._label_gauges()
        return slot

    @locked
    def set_label(self, label: str) -> bool:
        if label in self.label_slots:
            return False
        self._ensure_label(label)
        return True

    @locked
    def delete_label(self, label: str) -> bool:
        """Remove a label locally. In a cluster this MUST be applied on every
        replica (the reference routes delete_label as #@broadcast,
        classifier.idl): a one-replica delete would be resurrected with a
        zeroed master by the next mix's schema union, leaving that replica's
        weights permanently offset from its peers."""
        slot = self.label_slots.pop(label, None)
        if slot is None:
            return False
        # zero the slot so a future reuse starts clean
        st = self.state
        self.state = ops.ClassifierState(
            w=st.w.at[slot].set(0.0),
            dw=st.dw.at[slot].set(0.0),
            prec=st.prec if st.prec.shape == (1, 1) else st.prec.at[slot].set(1.0),
            dprec=st.dprec if st.dprec.shape == (1, 1) else st.dprec.at[slot].set(0.0),
        )
        self.label_counts[slot] = 0.0
        self._dcounts[slot] = 0.0
        self.labels[slot] = ""
        self._label_gauges()
        return True

    @locked
    def get_labels(self) -> Dict[str, int]:
        return {
            lab: int(self.label_counts[slot] + self._dcounts[slot])
            for lab, slot in self.label_slots.items()
        }

    # -- train / classify ----------------------------------------------------
    def featurize_train(self, data: Sequence[Tuple[str, Datum]]):
        """Stage-1 host featurization for the pipelined microbatch
        (server/microbatch.py PipelinedCoalescer): batch-convert WITHOUT
        the driver lock — the WeightManager has its own lock for the
        batch idf observe — so the next batch featurizes while the
        device consumes the previous one. Returns the (labels, idx, val)
        triple ``train_hashed`` consumes."""
        labels = [label for label, _ in data]
        csr = self.converter.convert_batch(
            [datum for _, datum in data], update_weights=True)
        sb = csr.to_padded()
        return labels, sb.idx, sb.val

    def train(self, data: Sequence[Tuple[str, Datum]]) -> int:
        """Batch-native train: one convert_batch sweep (memoized
        tokenization, single hash pass, batch idf observe) into the
        pre-hashed device path — no per-datum SparseVector objects.
        Featurization runs unlocked; train_hashed takes the driver lock
        for the device step (batch_bucket row padding lives there)."""
        if not data:
            return 0
        labels, idx, val = self.featurize_train(data)
        return self.train_hashed(labels, idx, val)

    def _train_slots(self, slots: np.ndarray, idx: np.ndarray,
                     val: np.ndarray, b: int, uniform: bool = False) -> int:
        """Shared pre-hashed dispatch tail: pow2 row bucketing (same shape
        buckets as the converter path), padding, and the device step. Both
        hashed entry points funnel here so their semantics cannot drift.

        ``uniform``: every row carries the same index row (a fixed key
        schema), so the step takes the dense [L, K]-submatrix plan
        (ops.train_batch_schema): 8.9 ms against 36.7 for the sparse plan
        at 8,192 x 40 and the same at 512 x 40, D = 2^25 (PERF.md section
        6, PR 28). Sequential train mode keeps the sparse scan, where
        exact per-datum semantics take priority.

        The shape a sparse flush is handed to one chip in follows its
        rows: as slabs where ``_cut_slabs`` says so, else as it came."""
        bsz = _bucket(b, 16)
        parallel = self.train_mode == "parallel"
        schema = uniform and parallel
        sharded = not schema and self._mesh is not None and parallel
        trace = self.trace
        owned = slabs = downer = None
        with self._span("step.train.stage"):
            if parallel and not (schema or sharded):
                slabs = _cut_slabs(idx, val, slots, bsz)
            if slabs is not None:
                sidx, sval, slots_arr, owner, n_slabs, entries = slabs
                didx, dval = jnp.asarray(sidx), jnp.asarray(sval)
                downer = jnp.asarray(owner)
            else:
                if bsz != b:  # zero rows are no-ops (x2 = 0 → alpha 0)
                    idx = np.pad(idx, ((0, bsz - b), (0, 0)))
                    val = np.pad(val, ((0, bsz - b), (0, 0)))
                slots_arr = np.zeros(bsz, dtype=np.int32)
                slots_arr[:b] = slots
            if sharded:
                # the flush is routed to its owners before it is uploaded:
                # a chip is handed its own entries alone, as local
                # columns, at the width of the fullest row any shard
                # holds. Among flushes of one width K that width never
                # shrinks in a server's life, so a short call that by
                # chance fills no row so far makes no second program
                # beside the one the traffic settled on; it is no wider
                # than K's own rung, so a narrow flush after a wide one
                # issues no more than it did under the mask
                from jubatus_tpu.parallel import sharded_model as _sm

                n_shards = self._mesh.shape[self._mesh_axis]
                k = idx.shape[1]
                ridx, rval, owned = _sm.route_rows(
                    idx, val, n_shards, self.converter.dim // n_shards,
                    self._shard_width.get(k, 0))
                self._shard_width[k] = ridx.shape[1]
                didx, dval = jax.device_put((ridx, rval),
                                            self._flush_sharding)
            elif slabs is None:
                didx = jnp.asarray(idx[0] if schema else idx)
                dval = jnp.asarray(val)
            dslots = jnp.asarray(slots_arr)
            mask = self._mask()
        plan = None
        # the program the flush runs: one a form and shape
        form = "schema" if schema else "mesh" if sharded \
            else "scan" if not parallel \
            else "rows" if slabs is None else "slabs"
        with self._span("step.train.dispatch"):
            if schema:
                plan = "schema"
                self.state = ops.train_batch_schema(
                    self.state, didx, dval, dslots, mask, self.param,
                    method=self.method)
            elif sharded:
                # shard_map path: one psum for the logits — weight state
                # never moves (ISSUE 13). Each shard settles its plan from
                # its own slice's shape and its own plane of the flush
                num_labels, dim = self.state.w.shape
                plan = ops.gather_plan(num_labels, dim // n_shards,
                                       ridx[0].size)
                self.state = _sm.train_batch(
                    self._mesh, self.state, didx, dval, dslots, mask,
                    self.param, method=self.method, axis=self._mesh_axis)
            else:
                # sequential mode keeps GSPMD partitioning of the placed
                # state
                if parallel:
                    plan = ops.gather_plan(*self.state.w.shape, didx.size)
                self.state = ops.train_batch(
                    self.state, didx, dval, dslots, mask, self.param,
                    method=self.method, mode=self.train_mode, owner=downer)
        self.event_model_updated(b)
        if trace is not None:
            if plan is not None:
                # which plan the step's rows and shapes settled on
                trace.count(f"step.train.plan_{plan}")
            # the flush as it arrives from the coalescer: the rows asked
            # for and the rows of their bucket, the width its requests
            # were packed at (each distinct one a shape the host pads
            # and concatenates to), the entries that carry a feature and
            # the entries the rows have at that width
            trace.count("step.train.rows", b)
            trace.count("step.train.rows_padded", bsz)
            trace.count(f"step.train.width_{idx.shape[1]}")
            if slabs is None:
                entries = int(np.count_nonzero(idx) if owned is None
                              else owned.sum())
            trace.count("step.train.entries", entries)
            trace.count("step.train.entries_padded", b * idx.shape[1])
            # what the device is handed: the bytes the stage put on it (a
            # routed flush's summed over the chips), the entries it
            # issues descriptors for, padding included, and the program
            # that runs them: each distinct key is one this server's
            # traffic made it compile
            trace.count("step.train.upload_bytes", sum(
                a.nbytes for a in (didx, dval, dslots, downer)
                if a is not None))
            trace.count("step.train.entries_issued", dval.size)
            shape = "x".join(map(str, dval.shape))
            trace.count(f"step.train.program_{form}_{shape}")
            if slabs is not None:
                # a cut flush: the slabs that carry a row's entries and
                # the bucket they ran in
                trace.count("step.train.slab_flushes")
                trace.count("step.train.slabs", n_slabs)
                trace.count("step.train.slabs_padded", dval.shape[0])
            if owned is not None:
                # the mesh's side: the chips issue shards x padded rows x
                # routed width descriptors for the entries that carry a
                # feature (what is left over is row padding: the fullest
                # row sets the width); the fullest shard's share says how
                # evenly the columns fall; the routed width is the mesh
                # program's, as width_<K> is the flush's own
                trace.count("step.train.shard_entries", int(owned.sum()))
                trace.count("step.train.shard_entries_issued", ridx.size)
                trace.count("step.train.shard_entries_owned_max",
                            int(owned.max()))
                trace.count(f"step.train.shard_width_{ridx.shape[1]}")
        return b

    @locked
    def train_hashed(self, labels: Sequence[str], idx: np.ndarray,
                     val: np.ndarray) -> int:
        """Train on pre-hashed features (the native ingest fast path,
        native/fast_ingest.cpp): ``idx``/``val`` are [B, K] arrays carrying
        exactly what converter.convert would have produced. Bypasses the
        converter entirely — callers must have established eligibility (no
        idf/user global weights; jubatus_tpu/native/ingest.py gates)."""
        if len(labels) == 0:
            return 0
        slots = [self._ensure_label(lb) for lb in labels]
        for s in slots:
            self._dcounts[s] += 1.0
        return self._train_slots(np.asarray(slots, dtype=np.int32),
                                 idx, val, len(labels))

    @locked
    def train_indexed(self, uniq_labels: Sequence[str], label_idx: np.ndarray,
                      idx: np.ndarray, val: np.ndarray) -> int:
        """Train on pre-hashed features with C++-deduplicated labels
        (native/fast_ingest.cpp): ``uniq_labels`` are the distinct label
        strings, ``label_idx`` the int32 [B] row->uniq mapping. The host
        loops only over the distinct set — vocabulary work is O(uniq),
        count bookkeeping is one bincount, so the GIL-bound cost per
        sample is constant regardless of batch size."""
        b = int(label_idx.shape[0])
        if b == 0:
            return 0
        slots_u = np.array([self._ensure_label(lb) for lb in uniq_labels],
                           dtype=np.int32)
        counts = np.bincount(label_idx, minlength=len(uniq_labels))
        # np.add.at, not fancy-index +=: the C++ parser MAY emit duplicate
        # uniq labels (past 256 distinct it appends without scanning), and
        # += keeps only the last write per duplicated slot
        np.add.at(self._dcounts, slots_u, counts[:len(slots_u)])
        # one shared index row: a cheap look at the second row settles it
        # for every feed that is not uniform
        uniform = bool((idx[1:2] == idx[0]).all() and (idx == idx[0]).all())
        return self._train_slots(slots_u[label_idx], idx, val, b, uniform)

    def classify(self, data: Sequence[Datum]) -> List[List[Tuple[str, float]]]:
        # deliberately NOT @locked: batch conversion touches no driver
        # state and classify_hashed takes the lock for exactly the
        # dispatch window — concurrent Datum-path queries overlap too
        if not data:
            return []
        sb = self.converter.convert_batch(data).to_padded(batch_bucket=16)
        out = self.classify_hashed(sb.idx, sb.val)
        if not out:
            return [[] for _ in data]
        # to_padded already row-bucketed; slice its pad rows back off
        return out[: len(data)]

    def classify_hashed(self, idx: np.ndarray,
                        val: np.ndarray) -> List[List[Tuple[str, float]]]:
        """Classify pre-hashed features (native ingest fast path); same
        output shape as classify().

        Dispatch-under-lock, wait-unlocked: the scores computation is
        ENQUEUED while the driver lock guarantees no train step can
        donate the state buffers first (train_batch donates for in-place
        scatters — dispatching against an already-donated Array raises
        "Array has been deleted"); once enqueued, the runtime keeps the
        buffers alive for the pending read, so the device round trip and
        result wait run unlocked and concurrent queries overlap instead
        of serializing. ≙ the reference's JRLOCK_ shared reads. H2D
        transfers touch no driver state: staged unlocked, so the
        critical section is just the enqueue."""
        n = idx.shape[0]
        if n == 0:
            return []
        b = _bucket(n, 16)
        with self._span("step.classify.stage"):
            if b != n:
                idx = np.pad(idx, ((0, b - n), (0, 0)))
                val = np.pad(val, ((0, b - n), (0, 0)))
            didx, dval = jnp.asarray(idx), jnp.asarray(val)
        with self._span("step.classify.lock_wait"):
            self.lock.acquire()
        try:
            if not self.label_slots:
                return [[] for _ in range(n)]
            slots = list(self.label_slots.items())
            with self._span("step.classify.dispatch"):
                if self._mesh is None:
                    pending = ops.scores(self.state, didx, dval, self._mask())
                else:
                    from jubatus_tpu.parallel import sharded_model as _sm

                    pending = _sm.scores(
                        self._mesh, self.state, didx, dval, self._mask(),
                        axis=self._mesh_axis)
        finally:
            self.lock.release()
        with self._span("step.classify.wait"):
            sc = np.asarray(pending)[:n]
        trace = self.trace
        if trace is not None:
            trace.count("step.classify.rows", n)
            trace.count("step.classify.rows_padded", b)
            # each distinct width is a scores program (times the row
            # buckets), as step.train.width_<K> is a train program
            trace.count(f"step.classify.width_{idx.shape[1]}")
        # (label, score) pairs are the answer as it goes on the wire: the
        # packer writes a tuple as it writes a list, so the service hands
        # these rows on as they are
        with self._span("classify.encode"):
            return [[(lab, float(row[slot]))
                     for lab, slot in slots] for row in sc]

    def shard_stats(self) -> Dict[str, Any]:
        """Feature-shard layout gauges (shard.* catalog rows,
        OBSERVABILITY.md §7): shard count + per-device weight-state
        bytes, and where each addressable shard of the weight table
        lives. Empty when unsharded."""
        if self._mesh is None:
            return {}
        n = self._mesh.shape[self._mesh_axis]
        total = sum(int(a.nbytes) for a in self.state)
        shards = self.state.w.addressable_shards
        return {"count": n, "rows": self.capacity,
                "bytes_in_use": total,
                "bytes_per_shard": total // n,
                "devices": [str(s.device) for s in shards],
                "shard_shape": list(shards[0].data.shape)}

    @locked
    def clear(self) -> None:
        self._init_model()
        self.converter.weights.clear()
        self.update_count = 0

    # -- mix plane -----------------------------------------------------------
    def get_schema(self) -> List[str]:
        return sorted(self.label_slots.keys())

    def sync_schema(self, union_schema: List[str]) -> None:
        """Realign label slots to the canonical (sorted union) vocabulary.

        After this, slot i holds union_schema[i] on every replica, so array
        diffs are row-aligned for the psum.

        Runs on EVERY mix prepare, so the already-aligned case (no new
        labels since the last round — every steady-state round) must be
        free: realigning unconditionally would drag all four
        [capacity, D] tables through host numpy each round (~2 GB of
        device→host→device traffic per member at D=2^24). When the
        slots DO move, rows are permuted on-device with a gather instead
        of round-tripping through the host.
        """
        new_cap = max(_INITIAL_CAPACITY, _next_pow2(len(union_schema)))
        target_slots = {lab: i for i, lab in enumerate(union_schema)}
        if new_cap == self.capacity and target_slots == self.label_slots:
            return  # already canonical — the steady-state mix round
        perm = np.full(new_cap, -1, dtype=np.int64)  # new slot -> old slot
        for new_slot, label in enumerate(union_schema):
            old = self.label_slots.get(label)
            if old is not None:
                perm[new_slot] = old
        live_h = perm >= 0
        gather = jnp.asarray(np.where(live_h, perm, 0).astype(np.int32))
        live_d = jnp.asarray(live_h)[:, None]

        def take_rows(a, fill):
            if a.shape == (1, 1):
                return a
            # device-side row permute: one gather + select, no host copy
            return jnp.where(live_d, a[gather], jnp.asarray(fill, a.dtype))

        st = self.state
        self.state = self._place(ops.ClassifierState(
            w=take_rows(st.w, 0.0),
            dw=take_rows(st.dw, 0.0),
            prec=take_rows(st.prec, 1.0),
            dprec=take_rows(st.dprec, 0.0),
        ))

        def take_vec(v):
            out = np.zeros(new_cap, dtype=v.dtype)
            live = perm >= 0
            out[live] = v[perm[live]]
            return out

        self.label_counts = take_vec(self.label_counts)
        self._dcounts = take_vec(self._dcounts)
        self.capacity = new_cap
        self.labels = list(union_schema) + [""] * (new_cap - len(union_schema))
        self.label_slots = {lab: i for i, lab in enumerate(union_schema)}
        self._label_gauges()

    def get_mixables(self):
        return {"classifier": _ClassifierMixable(self), "weights": self.converter.weights}

    # -- persistence ---------------------------------------------------------
    @locked
    def pack(self) -> Any:
        return {
            "method": self.method,
            "dim": self.converter.dim,
            "labels": self.labels,
            "capacity": self.capacity,
            "w": np.asarray(self.state.w + self.state.dw),
            "prec": np.asarray(self.state.prec + self.state.dprec),
            "label_counts": self.label_counts + self._dcounts,
            "weights": self.converter.weights.pack(),
        }

    @locked
    def unpack(self, obj: Any) -> None:
        saved_method = obj.get("method")
        if isinstance(saved_method, bytes):
            saved_method = saved_method.decode()
        if saved_method != self.method:
            raise ValueError(
                f"checkpoint method {saved_method!r} != driver method {self.method!r}"
            )
        if int(obj.get("dim", self.converter.dim)) != self.converter.dim:
            raise ValueError(
                f"checkpoint feature dim {obj['dim']} != driver dim "
                f"{self.converter.dim} (dim_bits mismatch)"
            )
        self.capacity = int(obj["capacity"])
        self.labels = [
            s.decode() if isinstance(s, bytes) else s for s in obj["labels"]
        ]
        self.label_slots = {lab: i for i, lab in enumerate(self.labels) if lab}
        self._label_gauges()
        # each leaf goes from the host to where it lies: on a mesh every
        # device is sent its own columns
        self._let_go()

        def put(a):
            return jax.device_put(np.asarray(a),
                                  self._leaf_sharding(np.shape(a)))

        def zeros(a):
            return jnp.zeros(np.shape(a), jnp.float32,
                             device=self._leaf_sharding(np.shape(a)))

        self.state = ops.ClassifierState(
            w=put(obj["w"]), dw=zeros(obj["w"]),
            prec=put(obj["prec"]), dprec=zeros(obj["prec"]))
        self.label_counts = np.asarray(obj["label_counts"], dtype=np.float32).copy()
        self._dcounts = np.zeros_like(self.label_counts)
        self.converter.weights.unpack(obj["weights"])

    @locked
    def get_status(self) -> Dict[str, Any]:
        st = super().get_status()
        st.update(
            method=self.method,
            num_labels=len(self.label_slots),
            label_capacity=self.capacity,
            num_features=self.converter.dim,
        )
        st.update({f"shard.{k}": v for k, v in self.shard_stats().items()})
        return st


class _ClassifierMixable:
    """Wraps the ops-level diff with the label-count vector."""

    def __init__(self, driver: ClassifierDriver):
        self._d = driver

    def get_diff(self):
        d = self._d
        diff = ops.get_diff(d.state)
        # ship only the ACTIVE label rows: the [capacity, D] tables are
        # pow2-padded (and capacities can diverge across replicas after
        # deletes), while the slot assignment is cluster-identical after
        # the round's schema sync — [n, D] is the same shape everywhere
        # and cuts the wire 4x at the bench shape (8-slot capacity, 2
        # labels). n = highest slot in use + 1, NOT len(labels): the
        # labels list is ""-padded to capacity by sync_schema. Slicing
        # clamps the (1, 1) no-confidence placeholders untouched.
        n = max(d.label_slots.values(), default=0) + 1
        if d._mesh is not None:
            # feature-sharded state ships PER-SHARD chunks keyed by start
            # column: each shard's slice copies out independently (no
            # full-matrix buffer) and enters the chunked/tiered/quantized
            # mix pipeline on its own. Peers fold chunk-wise — layouts
            # must match (assemble_chunks validates on apply).
            from jubatus_tpu.parallel import sharded_model as _sm

            chunked = {}
            for key in ("dw", "dprec"):
                a = diff[key]
                if a.ndim == 2 and a.shape[1] == d.converter.dim:
                    chunked[key] = _sm.shard_chunks(a, rows=n)
            diff = dict(diff, **chunked)
        elif n < diff["dw"].shape[0]:
            diff = dict(diff, dw=diff["dw"][:n], dprec=diff["dprec"][:n])
        diff["label_counts"] = d._dcounts[:n].copy()
        return diff

    def put_diff(self, diff) -> bool:
        d = self._d
        # the same reduced diff dict is applied to every replica — no mutation
        array_diff = {k: v for k, v in diff.items() if k != "label_counts"}
        array_diff = _assemble_sharded(d, array_diff, rank=2)
        d.state = ops.put_diff(d.state, array_diff)
        counts = diff.get("label_counts")
        if counts is not None:
            counts = np.asarray(counts)
            d.label_counts[:len(counts)] += counts
            d._dcounts[:] = 0.0
        return True


def _assemble_sharded(driver, array_diff: dict, rank: int) -> dict:
    """Reassemble per-shard wire chunks in a diff dict: back onto the
    receiving driver's shard devices when it is sharded (each chunk
    lands on its owner — no host concat of the full matrix), or into
    one host array when an unsharded replica receives a sharded peer's
    diff (mixed fleets stay correct, just not zero-copy)."""
    from jubatus_tpu.parallel import sharded_model as _sm

    out = dict(array_diff)
    for key, v in array_diff.items():
        if not _sm.is_chunked(v):
            continue
        if driver._mesh is not None:
            out[key] = _sm.assemble_chunks(
                v, _sm.chunk_sharding(driver._mesh, rank=rank,
                                      axis=driver._mesh_axis))
        else:
            items = sorted(
                ((int((k.decode() if isinstance(k, bytes) else k)[1:]), c)
                 for k, c in v.items()), key=lambda kv: kv[0])
            out[key] = np.concatenate([c for _, c in items], axis=-1)
    return out


def _cut_slabs(idx: np.ndarray, val: np.ndarray, slots: np.ndarray,
               bsz: int):
    """A flush ``[b, K]`` cut into slabs of ``_SLAB_WIDTH`` entries, or
    None where it runs as the rows it came as.

    The one rule, from what the flush shows: slabs where they issue fewer
    entries than the rows by a clear factor, ``S_b * W * _SLAB_GAIN <=
    bsz * K`` (``S_b`` the slabs that carry an entry, bucketed to a power
    of two, ``bsz`` the rows' own bucket). Rows no wider than ``W``, or
    whose width is no multiple of it, stay rows without a count (K = 40:
    a row is under one slab, nothing to win).

    A row's entries are packed from column 0 and column 0 of the hashed
    space is never a feature, so a slab carries entries iff its first
    column is non-zero: ``b * K / W`` elements read to decide. Returns
    ``(idx [S_b, W], val [S_b, W], labels [S_b], owner [S_b], slabs,
    entries)``: a slab's label is its document's, ``owner`` numbers the
    documents that have a slab in order (what ops.train_batch_parallel
    sums over), padding slabs are ``(0, 0.0)`` entries of a document of
    their own, the last, and ``entries`` the flush's that carry a feature.
    """
    b, k = idx.shape
    w = _SLAB_WIDTH
    if k <= w or k % w:
        return None
    shape = (b, k // w, w)
    idx3 = idx.reshape(shape)
    live = idx3[:, :, 0] != 0
    s_b = _bucket(int(np.count_nonzero(live)), 16)
    if s_b * w * _SLAB_GAIN > bsz * k:
        return None
    entries = int(np.count_nonzero(idx))
    cut = idx3[live]
    if np.count_nonzero(cut) != entries:
        # an entry zeroed in place (the ingest's finite screen writes
        # (0, 0.0) over a NaN) stood first in its slab: look at them all
        live = idx3.any(axis=2)
        cut = idx3[live]
        s_b = _bucket(len(cut), 16)
    n = len(cut)
    doc = np.nonzero(live)[0]
    sidx = np.zeros((s_b, w), np.int32)
    sval = np.zeros((s_b, w), np.float32)
    labels = np.zeros(s_b, np.int32)
    owner = np.full(s_b, s_b - 1, np.int32)
    sidx[:n] = cut
    sval[:n] = val.reshape(shape)[live]
    labels[:n] = slots[doc]
    owner[0] = 0
    owner[1:n] = np.cumsum(doc[1:] != doc[:-1])
    return sidx, sval, labels, owner, n, entries


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
