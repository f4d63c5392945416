"""Codec protocol + payload container for federated uplink/downlink traffic.

A ``Codec`` turns a param/delta pytree into a ``Payload`` — a bag of
*actually transmitted* arrays whose ``nbytes`` is measured from the buffer
dtypes (int8 codes count 1 byte, packed int4 nibbles half a byte, ...) —
and back.  Codecs are stateless objects; per-client compression state
(the error-feedback residual, the delta reference) is threaded
explicitly, so one codec instance serves every client:

    payload, state = codec.encode(tree, state, key=key)
    tree2 = codec.decode(payload)

One contract
------------
A codec implements its pure-``jnp`` flat-vector transform:
``encode_flat`` / ``decode_flat``, ``meta_static(d)`` (the meta dict
``encode_flat`` attaches, a function of d alone), ``nbytes_static(d)``
(the exact wire bytes of one payload; every shipped codec's buffer
shapes are functions of d, so byte accounting needs no payload) and
``bits_per_param``.  A stateful wrapper (``ErrorFeedback``,
``DeltaCodec``) adds only its state as an explicit pytree of arrays
(``init_state(s)_traced`` / ``state(s)_to_host``: host ``None`` and
traced zeros are the same state by construction) and its traced round
trip with that state.  ``QuantizeCodec`` overrides the stacked round
trip to run ONE kernel over all clients' concatenated blocks.

The base class derives everything else from that:

* the traced pair ``encode_decode_traced(flat, state, key)`` ->
  ``(wire arrays, decoded, new_state)`` and its stacked (C, d) twin
  (``vmap`` unless overridden), with the wire boundary marked by
  (best-effort) optimization barriers; ``roundtrip_traced[_stacked]``
  are the same pair without the wire arrays, for callers already under
  jit (the fused round scan, the downlink program);
* the host ``Payload`` boundary (``encode``, ``roundtrip``,
  ``roundtrip_flat``, ``encode_stacked``, ``roundtrip_stacked``): each
  call is ONE jitted program of the traced pair, and each ``Payload``
  is rebuilt from the wire arrays it returns plus ``meta_static(d)``.
  Host and traced round trips therefore run the same composition: a
  jitted traced call decodes bit for bit like the host boundary.  XLA
  CPU contracts a dequantize multiply into a following add/subtract (an
  fma) whenever both share a program, which no barrier prevents, so a
  host path of eager ops would drift; inside a larger program (the
  fused round scan) the delta reconstruction add may still contract
  differently, to within 1e-6.
"""
from __future__ import annotations

import dataclasses
import functools
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
    # prefix of the host boundary's program names (repro.obs.jitwatch)
    tag = "codec"

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
        """Exact wire bytes of one payload for a d-element flat vector
        (equal to ``Payload.nbytes``, without materializing a payload)."""
        raise NotImplementedError

    def meta_static(self, d: int) -> Dict[str, Any]:
        """The ``encode_flat`` meta dict for a d-element flat vector."""
        return {}

    # -- codec state (stateful wrappers override) ------------------------
    def init_state_traced(self, d: int, host_state=None):
        """Traced-state pytree for ONE stream (downlink broadcast)."""
        return ()

    def state_to_host(self, state):
        """Inverse of ``init_state_traced``."""
        return None

    def init_states_traced(self, d: int, host_states):
        """Stacked traced state for C client streams (uplink carry)."""
        return ()

    def states_to_host(self, states, n: int):
        return [None] * n

    # -- traced pair ------------------------------------------------------
    def _encode(self, flat, key):
        arrays, meta = self.encode_flat(flat, key=key)
        return Payload(self.name, arrays, {**meta, "d": int(flat.size)})

    def encode_decode_traced(self, flat: jnp.ndarray, state=(), *,
                             key=None):
        """In-graph encode + decode of one (d,) flat vector.

        Returns (wire arrays, decoded, new_state).  Both ends of the
        transform sit behind an optimization barrier, a best-effort
        marker of the wire boundary (see the module docstring for what
        it does not stop)."""
        payload = self._encode(jax.lax.optimization_barrier(flat), key)
        decoded = self.decode_flat(payload)[:flat.size]
        return payload.arrays, jax.lax.optimization_barrier(decoded), state

    def encode_decode_traced_stacked(self, flats: jnp.ndarray, states=(),
                                     *, keys=None):
        """``encode_decode_traced`` over the stacked (C, d) client axis:
        wire arrays with a leading (C,) axis and (C, d) decoded rows,
        row c bit-identical to the single call with ``keys[c]``.  The
        barriers sit outside the vmap (optimization_barrier has no
        batching rule)."""
        def one(f, k):
            payload = self._encode(f, k)
            return payload.arrays, self.decode_flat(payload)[:f.size]
        arrays, decoded = jax.vmap(one)(
            jax.lax.optimization_barrier(flats), keys)
        return arrays, jax.lax.optimization_barrier(decoded), states

    def split_stacked_arrays(self, arrays, c: int, d: int):
        """Per-client wire buffers of the stacked pair's arrays, in-graph
        (batch-shaped codecs override to slice their row layout)."""
        return [{k: v[i] for k, v in arrays.items()} for i in range(c)]

    def roundtrip_traced(self, flat: jnp.ndarray, state=(), *, key=None):
        """(decoded, new_state) of ``encode_decode_traced``."""
        _, decoded, state = self.encode_decode_traced(flat, state, key=key)
        return decoded, state

    def roundtrip_traced_stacked(self, flats: jnp.ndarray, states=(), *,
                                 keys=None):
        """(decoded, new_states) of ``encode_decode_traced_stacked``."""
        _, decoded, states = self.encode_decode_traced_stacked(
            flats, states, keys=keys)
        return decoded, states

    # -- host Payload boundary --------------------------------------------
    # the host programs are cached per codec instance (one instance
    # serves every client of a trainer, so each trainer compiles them
    # once); they are named programs of the device trace
    # (repro.obs.jitwatch), kept out of the engine's dispatch counts
    @functools.cached_property
    def _roundtrip_program(self):
        def roundtrip(flat, state, key):
            arrays, decoded, state = self.encode_decode_traced(
                flat, self.init_state_traced(flat.size, state), key=key)
            return arrays, decoded, self.state_to_host(state)
        return jitwatch.wrap(f"{self.tag}_roundtrip", roundtrip,
                             counted=False)

    @functools.cached_property
    def _stacked_program(self):
        # the per-client states and keys stack, and the per-client wire
        # buffers and states split, inside this one program
        def roundtrip_stacked(flats, states, keys):
            with jax.named_scope("uplink_codec"):
                c, d = flats.shape
                arrays, decoded, states = self.encode_decode_traced_stacked(
                    flats, self.init_states_traced(d, states),
                    keys=None if keys is None else jnp.asarray(keys))
                return (self.split_stacked_arrays(arrays, c, d), decoded,
                        self.states_to_host(states, c))
        return jitwatch.wrap(f"{self.tag}_roundtrip_stacked",
                             roundtrip_stacked, counted=False)

    @functools.cached_property
    def _empty_states(self):
        return {}

    def _host_state(self, d: int, state):
        """A stream's host state, with None (no upload yet) replaced by
        one empty state per width made once — so every call of a host
        program has one signature."""
        if state is not None:
            return state
        if d not in self._empty_states:
            self._empty_states[d] = self.state_to_host(
                self.init_state_traced(d))
        return self._empty_states[d]

    def _payload(self, arrays, spec: "TreeSpec", d: int) -> Payload:
        return Payload(self.name, dict(arrays),
                       {**self.meta_static(d), "spec": spec, "d": d})

    def roundtrip_flat(self, flat: jnp.ndarray, spec: "TreeSpec",
                       state=None, *, key=None):
        """Encode a pre-flattened (d,) vector and decode it as the
        receiver will: (payload, new_state, decoded_flat)."""
        d = int(flat.size)
        arrays, decoded, state = self._roundtrip_program(
            flat, self._host_state(d, state), key)
        return self._payload(arrays, spec, d), state, decoded

    def roundtrip(self, tree, state=None, *, key=None):
        """(payload, new_state, decoded_tree) of a pytree."""
        flat, spec = tree_to_flat(tree)
        payload, state, decoded = self.roundtrip_flat(flat, spec, state,
                                                      key=key)
        return payload, state, flat_to_tree(decoded, spec)

    def encode(self, tree, state=None, *, key=None
               ) -> Tuple[Payload, Optional[Any]]:
        payload, state, _ = self.roundtrip(tree, state, key=key)
        return payload, state

    def decode(self, payload: Payload):
        flat = self.decode_flat(payload)[:payload.meta["d"]]
        return flat_to_tree(flat, payload.meta["spec"])

    def roundtrip_stacked(self, flats: jnp.ndarray, spec: "TreeSpec",
                          states=None, *, keys=None):
        """``roundtrip_flat`` over the stacked (C, d) client axis, as ONE
        program: (payloads, new_states, (C, d) decoded), row i
        bit-identical to ``roundtrip_flat(flats[i], ..., key=keys[i])``.
        ``keys`` is a (C, 2) key array, a list of C keys, or None
        (round-to-nearest for every row); ``states`` a list of C host
        states (None before a client's first upload)."""
        c, d = flats.shape
        states = [None] * c if states is None else states
        arrays, decoded, states = self._stacked_program(
            flats, [self._host_state(d, s) for s in states], keys)
        return ([self._payload(a, spec, d) for a in arrays], list(states),
                decoded)

    def encode_stacked(self, flats: jnp.ndarray, spec: "TreeSpec",
                       states=None, *, keys=None):
        """(payloads, new_states) of ``roundtrip_stacked``."""
        payloads, states, _ = self.roundtrip_stacked(flats, spec, states,
                                                     keys=keys)
        return payloads, states


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


def _zeros_if_none(x, d: int):
    """A stream's (d,) state vector, or zeros for a stream with none yet
    (the same state by construction: x + 0 == x, x - 0 == x)."""
    return (jnp.zeros((d,), jnp.float32) if x is None
            else jnp.asarray(x, jnp.float32))


class _Wrapper(Codec):
    """A stateful codec around an inner codec whose wire format it
    sends unchanged."""

    def __init__(self, inner: Codec):
        self.inner = inner

    def decode_flat(self, payload):
        return self.inner.decode_flat(payload)

    def bits_per_param(self, d: int) -> float:
        return self.inner.bits_per_param(d)

    def nbytes_static(self, d: int) -> int:
        return self.inner.nbytes_static(d)

    def meta_static(self, d: int):
        return self.inner.meta_static(d)

    def split_stacked_arrays(self, arrays, c: int, d: int):
        return self.inner.split_stacked_arrays(arrays, c, d)


class ErrorFeedback(_Wrapper):
    """Residual-accumulating wrapper around a lossy inner codec.

    The client adds its accumulated residual before encoding and keeps
    the new residual (x + e) - decode(...) locally, so quantization /
    sparsification error is re-injected instead of lost — the standard
    EF trick that restores convergence under biased compressors (cf.
    PowerSGD / EF-SGD).  state is the residual flat vector (None or
    zeros before the first upload); decode is the inner codec's (the
    server never sees the residual).
    The host boundary's stacked program is the engine's uplink,
    ``ef_roundtrip_stacked``: residual add, the inner codec's batched
    encode and decode, the residual and the per-client split.
    """

    tag = "ef"

    def __init__(self, inner: Codec):
        super().__init__(inner)
        self.name = inner.name + "+ef"

    def init_state_traced(self, d: int, host_state=None):
        return _zeros_if_none(host_state, d)

    def state_to_host(self, state):
        return state

    def init_states_traced(self, d: int, host_states):
        return jnp.stack([self.init_state_traced(d, s)
                          for s in host_states])

    def states_to_host(self, states, n: int):
        return [states[i] for i in range(n)]

    def encode_decode_traced(self, flat, state, *, key=None):
        adj = flat + state
        arrays, dec, _ = self.inner.encode_decode_traced(adj, key=key)
        return arrays, dec, adj - dec

    def encode_decode_traced_stacked(self, flats, states, *, keys=None):
        adj = flats + states
        arrays, dec, _ = self.inner.encode_decode_traced_stacked(adj,
                                                                 keys=keys)
        return arrays, dec, adj - dec


class DeltaCodec(_Wrapper):
    """Broadcast the delta vs the last round's reconstruction (downlink).

    The server encodes θ_t − ref_{t-1} through the inner codec and both
    ends advance their reference to the *reconstruction* ref_t = ref_{t-1}
    + decode(payload), so a lossy inner codec never lets server and
    clients drift apart.  Round-to-round parameter deltas are orders of
    magnitude smaller than the weights themselves, so the inner
    quantizer's per-block scale (absmax/qmax) — and with it the
    distortion — shrinks accordingly at identical wire bytes.  The first
    transmission (reference zeros, host state None) carries the full
    parameters.

    state is the pair (reference flat vector, inner codec state); decode
    requires the receiver's reference, so this codec is only usable
    through the ``roundtrip*`` API (which the engine's downlink uses) —
    a bare ``decode`` raises.  In the async scheduler every version is
    encoded exactly once in order, so a client dispatched at version v
    receives the chain reconstruction ref_v regardless of which version
    it previously held (reliable cumulative-delta multicast).
    """

    tag = "delta"

    def __init__(self, inner: Codec):
        super().__init__(inner)
        self.name = "delta+" + inner.name

    def decode_flat(self, payload: Payload):
        raise NotImplementedError(
            "delta codec reconstruction needs the receiver's reference; "
            "use roundtrip/roundtrip_flat")

    def init_state_traced(self, d: int, host_state=None):
        ref, inner = (None, None) if host_state is None else host_state
        return (_zeros_if_none(ref, d), self.inner.init_state_traced(d, inner))

    def state_to_host(self, state):
        ref, inner = state
        return (ref, self.inner.state_to_host(inner))

    def init_states_traced(self, d: int, host_states):
        pairs = [(None, None) if s is None else s for s in host_states]
        return (jnp.stack([_zeros_if_none(ref, d) for ref, _ in pairs]),
                self.inner.init_states_traced(d, [i for _, i in pairs]))

    def states_to_host(self, states, n: int):
        refs, inner = states
        inner_host = self.inner.states_to_host(inner, n)
        return [(refs[i], inner_host[i]) for i in range(n)]

    def encode_decode_traced(self, flat, state, *, key=None):
        ref, inner_state = state
        arrays, dec_delta, inner_state = self.inner.encode_decode_traced(
            flat - ref, inner_state, key=key)
        decoded = ref + dec_delta
        return arrays, decoded, (decoded, inner_state)

    def encode_decode_traced_stacked(self, flats, states, *, keys=None):
        refs, inner_states = states
        arrays, dec_delta, inner_states = \
            self.inner.encode_decode_traced_stacked(
                flats - refs, inner_states, keys=keys)
        decoded = refs + dec_delta
        return arrays, decoded, (decoded, inner_states)
