"""Codec protocol + payload container for federated uplink/downlink traffic.

A ``Codec`` turns a param/delta pytree into a ``Payload`` — a bag of
*actually transmitted* arrays whose ``nbytes`` is measured from the buffer
dtypes (int8 codes count 1 byte, packed int4 nibbles half a byte, ...),
replacing the old f32-only ``tree_param_bytes`` assumption — and back.

Codecs are stateless objects; per-client compression state (the error
feedback residual) is threaded explicitly through ``encode`` so one codec
instance serves every client while residuals stay client-local:

    payload, state = codec.encode(tree, state, key=key)
    tree2 = codec.decode(payload)

``ErrorFeedback`` wraps any lossy codec: the client adds its accumulated
residual before encoding and keeps the new residual (x + e) - decode(...)
locally, so quantization/sparsification error is re-injected instead of
lost — the standard EF trick that restores convergence under biased
compressors (cf. PowerSGD / EF-SGD).

Traced codec contract (fused multi-round engine)
------------------------------------------------
Next to the host-boundary ``Payload`` API every codec exposes a fully
in-graph path the fused round scan uses:

* ``roundtrip_traced(flat, state, key)`` -> (decoded, new_state) keeps
  encode -> decode entirely inside the surrounding jit — the Payload
  buffers are graph intermediates that never reach the host;
* ``roundtrip_traced_stacked(flats, states, keys)`` is its (C, d)
  stacked-client twin (quantize codecs batch ONE kernel over all rows);
* codec state is an explicit pytree of arrays so it can ride a
  ``lax.scan`` carry: ``init_state_traced`` / ``init_states_traced``
  build it from the host-format state (None -> zeros — equivalent by
  construction), ``state_to_host`` / ``states_to_host`` convert back;
* ``nbytes_static(d)`` is the exact wire size of one payload for a
  d-element flat vector.  Every shipped codec has data-INdependent
  payload sizes (codes/scales/index/value buffer shapes are functions of
  d alone), so the comms ledger and the scheduler's time models keep
  exact byte accounting without a device->host sync per round.
  ``tests/test_fed_fused.py`` pins ``nbytes_static == Payload.nbytes``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import jitwatch


@dataclasses.dataclass
class Payload:
    """What actually crosses the wire: named buffers + static metadata.

    ``meta`` (treedef, shapes, codec params) is O(#leaves) python data —
    negligible next to the O(d) buffers and excluded from the byte count.
    """
    kind: str
    arrays: Dict[str, jnp.ndarray]
    meta: Dict[str, Any]

    @property
    def nbytes(self) -> int:
        return int(sum(a.size * a.dtype.itemsize
                       for a in self.arrays.values()))

    @property
    def nbytes_entropy(self) -> int:
        """Size estimate under an ideal entropy coder (host-side, lazy).

        The discrete code buffers are charged their empirical zeroth-order
        entropy instead of their fixed-width layout — int4/topk codes are
        far from uniform, so this quantifies the headroom a real range
        coder would buy.  f32 side buffers (scales, kept values, sketch
        factors) stay at their raw size; codecs whose buffers are all f32
        report ``nbytes`` unchanged.
        """
        bits = self.meta.get("bits")
        if bits in (4, 8):
            codes = np.asarray(self.arrays["codes"])
            if bits == 4:                 # nibble symbols, not packed bytes
                u = codes.astype(np.uint8)
                codes = np.concatenate([u >> 4, u & 0xF], axis=None)
            code_bytes = -(-_entropy_total_bits(codes) // 8)
            return int(code_bytes + self.arrays["scales"].size
                       * self.arrays["scales"].dtype.itemsize)
        if "indices" in self.arrays:      # topk: gap-coded sorted indices
            idx = np.asarray(self.arrays["indices"], np.int64)
            gaps = np.diff(idx, prepend=0)
            idx_bytes = -(-_entropy_total_bits(gaps) // 8)
            vals = self.arrays["values"]
            return int(idx_bytes + vals.size * vals.dtype.itemsize)
        return self.nbytes


def _entropy_total_bits(symbols) -> int:
    """Total bits of a symbol array under its empirical distribution."""
    _, counts = np.unique(np.asarray(symbols).ravel(), return_counts=True)
    p = counts / counts.sum()
    return int(np.ceil(float(-(p * np.log2(p)).sum()) * counts.sum()))


@dataclasses.dataclass(frozen=True)
class TreeSpec:
    """Enough structure to rebuild a pytree from a flat f32 vector."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]

    @property
    def size(self) -> int:
        out = 0
        for s in self.shapes:
            n = 1
            for x in s:
                n *= x
            out += n
        return out


def tree_to_flat(tree) -> Tuple[jnp.ndarray, TreeSpec]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec = TreeSpec(treedef, tuple(l.shape for l in leaves),
                    tuple(l.dtype for l in leaves))
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1)
                            for l in leaves])
    return flat, spec


def flat_to_tree(flat: jnp.ndarray, spec: TreeSpec):
    leaves, off = [], 0
    for shape, dtype in zip(spec.shapes, spec.dtypes):
        n = 1
        for s in shape:
            n *= s
        leaves.append(flat[off:off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


class Codec:
    """Base codec: subclasses implement the flat-vector transform."""

    name = "codec"
    stateful = False
    # the flat-vector transform is pure jnp (jit-safe), so the fused
    # round scan may inline encode->decode via the traced API below
    traceable = True
    # ... and the jitted ``roundtrip_traced`` decodes bit-identically to
    # the host boundary, so the per-round engine runs its downlink round
    # trip as one program (codecs whose traced decode drifts keep the
    # host path)
    traced_matches_host = True

    # -- flat-vector transform (override) -------------------------------
    def encode_flat(self, flat: jnp.ndarray, *, key=None
                    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, Any]]:
        raise NotImplementedError

    def decode_flat(self, payload: Payload) -> jnp.ndarray:
        raise NotImplementedError

    def bits_per_param(self, d: int) -> float:
        """Analytic uplink cost model (exact for the buffer layout)."""
        raise NotImplementedError

    def nbytes_static(self, d: int) -> int:
        """Exact wire bytes of one payload for a d-element flat vector.

        All shipped codecs have data-independent payload sizes, so this
        equals ``Payload.nbytes`` without materializing a payload — the
        fused multi-round engine accounts bytes from it with zero host
        syncs.  Subclasses whose layout differs from a pure
        bits-per-param model (padding, per-block scales) override it.
        """
        raise NotImplementedError

    def meta_static(self, d: int) -> Dict[str, Any]:
        """The ``encode_flat`` meta dict for a d-element flat vector.

        Shipped codecs' meta is a pure function of d and the codec
        params (like ``nbytes_static``), which lets ``ErrorFeedback``
        rebuild exact Payloads from in-graph encode outputs without a
        second host-side encode.  Codecs whose ``encode_flat`` attaches
        meta must override this to match it.
        """
        return {}

    def _flat_payload(self, flat: jnp.ndarray, spec: "TreeSpec", *,
                      key=None) -> Payload:
        arrays, meta = self.encode_flat(flat, key=key)
        meta["spec"] = spec
        meta["d"] = int(flat.size)
        return Payload(self.name, arrays, meta)

    # -- pytree API -----------------------------------------------------
    def encode(self, tree, state=None, *, key=None
               ) -> Tuple[Payload, Optional[Any]]:
        flat, spec = tree_to_flat(tree)
        return self._flat_payload(flat, spec, key=key), state

    def decode(self, payload: Payload):
        flat = self.decode_flat(payload)[:payload.meta["d"]]
        return flat_to_tree(flat, payload.meta["spec"])

    def roundtrip(self, tree, state=None, *, key=None):
        """encode + what the receiver will decode, in one call.

        Returns (payload, new_state, decoded_tree).  ErrorFeedback
        overrides this to reuse the decode it already computed for the
        residual instead of running a second O(d) decode.
        """
        payload, new_state = self.encode(tree, state, key=key)
        return payload, new_state, self.decode(payload)

    # -- pre-flattened API ----------------------------------------------
    def roundtrip_flat(self, flat: jnp.ndarray, spec: "TreeSpec",
                       state=None, *, key=None):
        """Per-client Payload boundary for pre-flattened uplinks.

        The vectorized engine flattens all C client deltas in ONE batched
        tree op and hands each codec a (d,) f32 row plus the shared
        ``TreeSpec``, skipping C per-client ``tree_to_flat``/
        ``flat_to_tree`` passes.  Returns (payload, new_state,
        decoded_flat) — byte-identical payloads to ``roundtrip``.
        """
        payload = self._flat_payload(flat, spec, key=key)
        return payload, state, self.decode_flat(payload)[:flat.size]

    # -- stacked-client API ---------------------------------------------
    def encode_stacked(self, flats: jnp.ndarray, spec: "TreeSpec",
                       states=None, *, keys=None):
        """Encode all C client rows of a (C, d) stacked flat array.

        Returns (payloads, new_states) — one Payload per client,
        byte-identical to C per-client ``encode``/``roundtrip_flat``
        calls with the same per-client keys.  The base implementation
        loops; batch-shaped codecs (int8/int4) override it to run ONE
        kernel dispatch over the stacked axis (the cohort dispatch path).
        """
        c = flats.shape[0]
        states = list(states) if states is not None else [None] * c
        keys = list(keys) if keys is not None else [None] * c
        payloads = [self._flat_payload(flats[i], spec, key=keys[i])
                    for i in range(c)]
        return payloads, states

    def roundtrip_stacked(self, flats: jnp.ndarray, spec: "TreeSpec",
                          states=None, *, keys=None):
        """``roundtrip_flat`` over the stacked client axis.

        Returns (payloads, new_states, decoded) with decoded shaped
        (C, d).  The base implementation threads per-client state through
        C ``roundtrip_flat`` calls — exact for any codec, including
        stateful wrappers; quantize codecs override with a batched
        single-dispatch path.
        """
        c = flats.shape[0]
        states = list(states) if states is not None else [None] * c
        keys = list(keys) if keys is not None else [None] * c
        payloads, new_states, decs = [], [], []
        for i in range(c):
            p, s, d = self.roundtrip_flat(flats[i], spec, states[i],
                                          key=keys[i])
            payloads.append(p)
            new_states.append(s)
            decs.append(d)
        return payloads, new_states, jnp.stack(decs)

    # -- traced (in-graph) API -------------------------------------------
    # See the module docstring: encode -> decode stays inside the caller's
    # jit, codec state is an explicit pytree of arrays (scan-carry ready),
    # and byte accounting comes from nbytes_static instead of a payload.

    def init_state_traced(self, d: int, host_state=None):
        """Traced-state pytree for ONE stream (downlink broadcast)."""
        return ()

    def state_to_host(self, state):
        """Inverse of ``init_state_traced`` after the fused run."""
        return None

    def init_states_traced(self, d: int, host_states):
        """Stacked traced state for C client streams (uplink carry)."""
        return ()

    def states_to_host(self, states, n: int):
        return [None] * n

    def roundtrip_traced(self, flat: jnp.ndarray, state=(), *, key=None):
        """In-graph encode + decode of one (d,) flat vector.

        Returns (decoded, new_state).  The default reuses the flat-vector
        transform — exact for stateless codecs; stateful wrappers
        (ErrorFeedback / DeltaCodec) override with explicit array state.
        The intermediate Payload holds tracers and never reaches the
        host; its static meta (shapes, d) is resolved at trace time.

        Both ends of the transform sit behind an optimization barrier, a
        best-effort marker of the wire boundary (on a real wire the
        payload bits ARE materialized).  Note the barrier does NOT stop
        XLA:CPU's fma/fms contraction across it — which is why the
        consumers that need bit-parity with the host boundary (the EF
        residual, see ``ErrorFeedback``) compute their arithmetic in the
        same jitted composition on both paths instead of relying on it.
        """
        decoded, state = self._roundtrip_traced_raw(
            jax.lax.optimization_barrier(flat), state, key=key)
        return jax.lax.optimization_barrier(decoded), state

    def _roundtrip_traced_raw(self, flat, state, *, key=None):
        payload = self._flat_payload(flat, None, key=key)
        return self.decode_flat(payload)[:flat.size], state

    def encode_decode_traced(self, flat: jnp.ndarray, *, key=None):
        """In-graph encode + decode that ALSO returns the wire buffers.

        Returns (payload arrays, decoded) with the exact barrier
        placement of ``roundtrip_traced`` — the decoded value is
        bit-identical to it — plus the payload's array dict as graph
        outputs, so a caller under jit can materialize the wire bytes
        from the SAME encode that produced the decode (the single-encode
        uplink: see ``ErrorFeedback.roundtrip_flat``).
        """
        payload = self._flat_payload(jax.lax.optimization_barrier(flat),
                                     None, key=key)
        decoded = self.decode_flat(payload)[:flat.size]
        return payload.arrays, jax.lax.optimization_barrier(decoded)

    def roundtrip_traced_stacked(self, flats: jnp.ndarray, states=(), *,
                                 keys=None):
        """``roundtrip_traced`` over the stacked (C, d) client axis.

        Row c is bit-identical to ``roundtrip_traced(flats[c], ...,
        key=keys[c])``; quantize codecs override with the single batched
        kernel dispatch the host-boundary stacked path uses.  The wire
        barriers sit OUTSIDE the vmap (optimization_barrier has no
        batching rule).
        """
        def one(f, k, s):
            return self._roundtrip_traced_raw(f, s, key=k)
        decoded, states = jax.vmap(one)(
            jax.lax.optimization_barrier(flats), keys, states)
        return jax.lax.optimization_barrier(decoded), states

    def encode_decode_traced_stacked(self, flats: jnp.ndarray, *,
                                     keys=None):
        """``encode_decode_traced`` over the stacked (C, d) client axis.

        Returns (payload arrays with a leading (C,) axis, (C, d)
        decoded); decoded rows are bit-identical to
        ``roundtrip_traced_stacked``'s.  ``keys`` must be a per-client
        key array (callers with None keys take the per-row host path).
        """
        def one(f, k):
            payload = self._flat_payload(f, None, key=k)
            return payload.arrays, self.decode_flat(payload)[:f.size]
        arrays, decoded = jax.vmap(one)(
            jax.lax.optimization_barrier(flats), keys)
        return arrays, jax.lax.optimization_barrier(decoded)

    def split_stacked_arrays(self, arrays, c: int, d: int):
        """Per-client wire buffers of ``encode_decode_traced_stacked``'s
        array outputs, in-graph (leading (C,) axis layout; batch-shaped
        codecs override to slice their concatenated-row layout)."""
        return [{k: v[i] for k, v in arrays.items()} for i in range(c)]


class IdentityCodec(Codec):
    """Raw f32 — the baseline every ratio in the benchmarks is against."""

    name = "identity"

    def encode_flat(self, flat, *, key=None):
        return {"values": flat.astype(jnp.float32)}, {}

    def decode_flat(self, payload):
        return payload.arrays["values"]

    def bits_per_param(self, d: int) -> float:
        return 32.0

    def nbytes_static(self, d: int) -> int:
        return 4 * d


class ErrorFeedback(Codec):
    """Residual-accumulating wrapper around a lossy inner codec.

    state is the client-local residual flat vector (starts at zero);
    decode is the inner codec's (the server never sees the residual).

    The whole uplink — residual add, inner encode, decode, residual
    update — runs inside ONE jitted program, for three reasons: it is
    one dispatch instead of a chain of eager ops; each uplink encodes
    exactly ONCE (the payload's wire buffers are outputs of the same
    in-graph encode that produced the decode — no eager re-encode); and
    — decisively — XLA CPU contracts the dequantize multiply into the
    residual subtract (an fms) whenever both sit in the same program,
    which no barrier prevents.  Computing the residual the same way on
    the host boundary and inside the fused round scan keeps the two
    engines bit-identical.  Payloads are rebuilt host-side from the
    returned arrays + the inner codec's static meta
    (``Codec.meta_static``), byte-identical to an eager encode.
    """

    stateful = True

    def __init__(self, inner: Codec):
        self.inner = inner
        self.name = inner.name + "+ef"
        self._rt_flat_jit = None
        self._rt_stacked_jit = None
        self._zeros = {}

    @property
    def traced_matches_host(self):
        return self.inner.traced_matches_host

    # jitted handles are cached per codec instance (one instance serves
    # every client of a trainer, so each trainer compiles these once);
    # they are named programs of the device trace (repro.obs.jitwatch),
    # kept out of the engine's dispatch counts
    def _jit_rt_flat(self):
        if self._rt_flat_jit is None:
            def fn(f, s, k):
                adj = f + s
                arrays, dec = self.inner.encode_decode_traced(adj, key=k)
                return arrays, dec, adj - dec
            self._rt_flat_jit = jitwatch.wrap("ef_roundtrip_flat", fn,
                                              counted=False)
        return self._rt_flat_jit

    def _jit_rt_stacked(self):
        # the stacked round trip is the engine's uplink: the C residual
        # rows and keys stack, and the per-client wire buffers and new
        # residuals split, inside this one program
        if self._rt_stacked_jit is None:
            def fn(f, states, keys):
                with jax.named_scope("uplink_codec"):
                    c, d = f.shape
                    adj = f + jnp.stack(states)
                    arrays, dec = self.inner.encode_decode_traced_stacked(
                        adj, keys=jnp.asarray(keys))
                    res = adj - dec
                    return (self.inner.split_stacked_arrays(arrays, c, d),
                            dec, [res[i] for i in range(c)])
            self._rt_stacked_jit = jitwatch.wrap(
                "ef_roundtrip_stacked", fn, counted=False)
        return self._rt_stacked_jit

    def _zero_residual(self, d: int):
        """The residual of a client that has not uploaded yet: one zero
        row per width, made once and passed for every such client."""
        if d not in self._zeros:
            self._zeros[d] = jnp.zeros((d,), jnp.float32)
        return self._zeros[d]

    def encode(self, tree, state=None, *, key=None):
        flat, spec = tree_to_flat(tree)
        payload, residual, _ = self.roundtrip_flat(flat, spec, state,
                                                   key=key)
        return payload, residual

    def roundtrip(self, tree, state=None, *, key=None):
        flat, spec = tree_to_flat(tree)
        payload, residual, decoded = self.roundtrip_flat(flat, spec,
                                                         state, key=key)
        return payload, residual, flat_to_tree(decoded, spec)

    def roundtrip_flat(self, flat, spec, state=None, *, key=None):
        st = jnp.zeros_like(flat) if state is None else state
        arrays, decoded, residual = self._jit_rt_flat()(flat, st, key)
        d = int(flat.size)
        payload = Payload(self.inner.name, dict(arrays),
                          {**self.inner.meta_static(d),
                           "spec": spec, "d": d})
        return payload, residual, decoded

    def roundtrip_stacked(self, flats, spec, states=None, *, keys=None):
        """Residual add + batched inner encode over the stacked axis.

        Row i is bit-identical to ``roundtrip_flat(flats[i], ...,
        states[i], key=keys[i])`` — residual accumulation is elementwise,
        so stacking commutes with it.  ``keys`` is a (C, 2) key array or
        a list of C keys; ``states`` a list of C residuals (None before
        a client's first upload).  The host does no stacking or slicing:
        the program returns each client's wire buffers and residual."""
        c, d = flats.shape
        states = list(states) if states is not None else [None] * c
        if keys is None or (isinstance(keys, (list, tuple))
                            and any(k is None for k in keys)):
            # per-row base loop keeps the None-key (deterministic
            # rounding) semantics of the inner codec
            return super().roundtrip_stacked(flats, spec, states,
                                             keys=keys)
        sts = [self._zero_residual(d) if s is None else s for s in states]
        arrays, decoded, residuals = self._jit_rt_stacked()(flats, sts,
                                                            keys)
        meta = {**self.inner.meta_static(d), "spec": spec, "d": d}
        payloads = [Payload(self.inner.name, a, dict(meta))
                    for a in arrays]
        return payloads, list(residuals), decoded

    def encode_stacked(self, flats, spec, states=None, *, keys=None):
        payloads, new_states, _ = self.roundtrip_stacked(
            flats, spec, states, keys=keys)
        return payloads, new_states

    # -- traced API: the residual is the state array ---------------------
    # A host state of None and a traced state of zeros are the same
    # residual by construction (x + 0 == x), so the conversions are
    # lossless in both directions.

    def init_state_traced(self, d: int, host_state=None):
        return (jnp.zeros((d,), jnp.float32) if host_state is None
                else jnp.asarray(host_state, jnp.float32))

    def state_to_host(self, state):
        return state

    def init_states_traced(self, d: int, host_states):
        return jnp.stack([self.init_state_traced(d, s)
                          for s in host_states])

    def states_to_host(self, states, n: int):
        return [states[i] for i in range(n)]

    def roundtrip_traced(self, flat, state, *, key=None):
        adj = flat + state
        dec, _ = self.inner.roundtrip_traced(adj, (), key=key)
        return dec, adj - dec

    def roundtrip_traced_stacked(self, flats, states, *, keys=None):
        with jax.named_scope("uplink_codec"):
            adj = flats + states
            dec, _ = self.inner.roundtrip_traced_stacked(adj, (), keys=keys)
            return dec, adj - dec

    def decode(self, payload: Payload):
        return self.inner.decode(payload)

    def encode_flat(self, flat, *, key=None):
        return self.inner.encode_flat(flat, key=key)

    def decode_flat(self, payload):
        return self.inner.decode_flat(payload)

    def bits_per_param(self, d: int) -> float:
        return self.inner.bits_per_param(d)

    def nbytes_static(self, d: int) -> int:
        return self.inner.nbytes_static(d)

    def meta_static(self, d: int):
        return self.inner.meta_static(d)


class DeltaCodec(Codec):
    """Broadcast the delta vs the last round's reconstruction (downlink).

    The server encodes θ_t − ref_{t-1} through the inner codec and both
    ends advance their reference to the *reconstruction* ref_t = ref_{t-1}
    + decode(payload), so a lossy inner codec never lets server and
    clients drift apart.  Round-to-round parameter deltas are orders of
    magnitude smaller than the weights themselves, so the inner
    quantizer's per-block scale (absmax/qmax) — and with it the
    distortion — shrinks accordingly at identical wire bytes.  The first
    transmission (ref = None) carries the full parameters.

    state is the pair (reference flat vector, inner codec state); decode
    requires the receiver's reference, so this codec is only usable
    through the ``roundtrip*`` API (which the engine's downlink uses) —
    a bare ``decode`` raises.  In the async scheduler every version is
    encoded exactly once in order, so a client dispatched at version v
    receives the chain reconstruction ref_v regardless of which version
    it previously held (reliable cumulative-delta multicast).
    """

    stateful = True
    # the in-graph reconstruction add contracts into an fma, so the traced
    # decode matches the host boundary to 1e-6, not bit for bit
    traced_matches_host = False

    def __init__(self, inner: Codec):
        self.inner = inner
        self.name = "delta+" + inner.name

    def roundtrip_flat(self, flat, spec, state=None, *, key=None):
        ref, inner_state = (None, None) if state is None else state
        base = jnp.zeros_like(flat) if ref is None else ref
        payload, inner_state, dec_delta = self.inner.roundtrip_flat(
            flat - base, spec, inner_state, key=key)
        decoded = base + dec_delta
        return payload, (decoded, inner_state), decoded

    def roundtrip(self, tree, state=None, *, key=None):
        flat, spec = tree_to_flat(tree)
        payload, new_state, decoded = self.roundtrip_flat(flat, spec, state,
                                                          key=key)
        return payload, new_state, flat_to_tree(decoded, spec)

    def encode(self, tree, state=None, *, key=None):
        payload, new_state, _ = self.roundtrip(tree, state, key=key)
        return payload, new_state

    def decode(self, payload: Payload):
        raise NotImplementedError(
            "delta codec reconstruction needs the receiver's reference; "
            "use roundtrip/roundtrip_flat")

    def decode_flat(self, payload: Payload):
        raise NotImplementedError(
            "delta codec reconstruction needs the receiver's reference; "
            "use roundtrip/roundtrip_flat")

    # -- traced API: state = (reference reconstruction, inner state) -----
    # A host reference of None and a traced reference of zeros encode the
    # same first transmission (flat - 0 is the full parameters).

    def init_state_traced(self, d: int, host_state=None):
        ref, inner = (None, None) if host_state is None else host_state
        ref = (jnp.zeros((d,), jnp.float32) if ref is None
               else jnp.asarray(ref, jnp.float32))
        return (ref, self.inner.init_state_traced(d, inner))

    def state_to_host(self, state):
        ref, inner = state
        return (ref, self.inner.state_to_host(inner))

    def init_states_traced(self, d: int, host_states):
        refs, inners = [], []
        for s in host_states:
            ref, inner = self.init_state_traced(d, s)
            refs.append(ref)
            inners.append(inner)
        # inner states are () for every shipped inner codec family except
        # EF, whose residual rows stack
        inner_stacked = (() if (not inners or isinstance(inners[0], tuple))
                         else jnp.stack(inners))
        return (jnp.stack(refs), inner_stacked)

    def states_to_host(self, states, n: int):
        refs, inner = states
        inner_host = self.inner.states_to_host(inner, n)
        return [(refs[i], inner_host[i]) for i in range(n)]

    def roundtrip_traced(self, flat, state, *, key=None):
        ref, inner_state = state
        dec_delta, inner_state = self.inner.roundtrip_traced(
            flat - ref, inner_state, key=key)
        decoded = ref + dec_delta
        return decoded, (decoded, inner_state)

    def roundtrip_traced_stacked(self, flats, states, *, keys=None):
        refs, inner_states = states
        dec_delta, inner_states = self.inner.roundtrip_traced_stacked(
            flats - refs, inner_states, keys=keys)
        decoded = refs + dec_delta
        return decoded, (decoded, inner_states)

    def bits_per_param(self, d: int) -> float:
        return self.inner.bits_per_param(d)

    def nbytes_static(self, d: int) -> int:
        return self.inner.nbytes_static(d)

    def meta_static(self, d: int):
        return self.inner.meta_static(d)
