"""The jax-traceability probe: certify numeric map/filter chains
device-lowerable by abstract evaluation (the DrJAX recipe, arXiv
2403.07128 — trace the primitives through JAX's abstract interpreter
instead of maintaining an allowlist).

``chain_claims`` inspects a (possibly fused) mapper chain: every leaf
must be a value-wise RecordOp (``ValueMap``/``Filter``; identity links
drop out), every UDF must classify pure + deterministic
(:mod:`.props`), and every UDF must *trace*: ``jax.eval_shape`` over a
``ShapeDtypeStruct`` lane must produce an elementwise result (same
leading shape; numeric out for maps, bool/integer out for filters)
without concretization errors.  A chain that passes is **certified**:
:mod:`dampr_tpu.plan.lower` assigns it ``exec_target="device"`` and the
runner executes it as one vectorized lane program instead of per-record
Python.

Execution semantics (the exactness contract, docs/analysis.md):

- The authoritative result is the **vectorized host evaluation** of the
  same certified program over the lane upcast to 64-bit — element-for-
  element what the per-record Python path computes (records box to
  Python int/float, i.e. 64-bit, on the host path; the upcast mirrors
  that).  Masks apply at the end: a certified elementwise op applied to
  a record a prior filter dropped cannot change surviving records.
- The **device dispatch** runs the identical program under ``jax.jit``
  (32-bit compute when ``jax_enable_x64`` is off, gated on the lane
  fitting int32) and is *verified per block* against the host
  evaluation; a mismatch silently keeps the host result and counts a
  fallback — the same fall-back-per-batch discipline as the lowered
  scanner programs' collision check.  Until a real-hardware trajectory
  justifies trusting unverified XLA output, the verify pass rides along
  (float lanes therefore skip dispatch when x64 is off: 32-bit rounding
  would fail verification every block).
- Residual risk, documented: Python ints are arbitrary-precision and
  int64 lane arithmetic wraps where per-record Python would grow a
  bignum.  The first batch of every lowered stage is additionally
  differential-tested against the per-record path at the runner level.
"""

import itertools
import logging
import threading
import weakref

import numpy as np

from .. import settings

log = logging.getLogger("dampr_tpu.analyze.jaxtrace")

_CERT_LOCK = threading.Lock()
_CERT_CACHE = weakref.WeakKeyDictionary()  # f -> {"map": ok, "filter": ok,
#                                                "why": str}

#: Lane dtypes the vectorized executor accepts (what Python-built blocks
#: actually carry, plus the narrow lanes block mappers emit).
_LANE_DTYPES = ("int64", "int32", "float64", "float32")

_INT32_MAX = np.int64(2 ** 31 - 1)
_INT32_MIN = np.int64(-(2 ** 31))


def _eval_ok(f, dtype, kind):
    """Abstract-eval ``f`` over an (8,) lane of ``dtype``; returns None
    on success or the reason string."""
    import jax

    try:
        out = jax.eval_shape(f, jax.ShapeDtypeStruct((8,), dtype))
    except Exception as e:  # noqa: BLE001 - any trace failure is the answer
        return "{}: {}".format(type(e).__name__, str(e)[:160])
    ok_shapes = (((8,), ()) if kind == "value" else ((8,),))
    if not hasattr(out, "shape") or tuple(out.shape) not in ok_shapes:
        return "not elementwise: input (8,) -> output {!r}".format(
            getattr(out, "shape", type(out).__name__))
    odt = np.dtype(out.dtype)
    if kind == "filter":
        if odt != np.dtype(bool) and odt.kind not in ("i", "u"):
            return "filter predicate traced to dtype {} (need bool/int)" \
                .format(odt)
    elif odt.kind not in ("i", "u", "f", "b"):
        return "map traced to non-numeric dtype {}".format(odt)
    return None


def certify_callable(f, kind):
    """Is ``f`` jax-traceable as an elementwise lane ``kind`` ("map" /
    "filter")?  Returns ``(ok, why)``; cached per function object."""
    with _CERT_LOCK:
        try:
            hit = _CERT_CACHE.get(f)
        except TypeError:
            hit = None  # unweakrefable callable (e.g. __slots__)
        if hit is not None and kind in hit:
            return hit[kind], hit.get("why_" + kind, "")
    import numpy as _np

    reasons = []
    ok = False
    for dt in (_np.int32, _np.float32):
        why = _eval_ok(f, dt, kind)
        if why is None:
            ok = True
        else:
            reasons.append(why)
    why = "" if ok else "; ".join(reasons[:1])
    try:
        with _CERT_LOCK:
            entry = _CERT_CACHE.setdefault(f, {})
            entry[kind] = ok
            entry["why_" + kind] = why
    except TypeError:
        pass  # unweakrefable callable: skip the cache
    return ok, why


class ChainSpec(object):
    """A certified chain: ordered ``(kind, f)`` lane ops, plus an
    optional trailing re-key — ``rekey`` is ``(key_f, value_f_or_None)``
    when the chain ends in a certified ``Rekey`` (the re-key every
    ``fold_by``/``count``/``a_group_by`` plants), so a numeric chain can
    feed a keyed device fold without leaving the lane program."""

    __slots__ = ("ops", "names", "rekey")

    def __init__(self, ops, names, rekey=None):
        self.ops = ops
        self.names = names
        self.rekey = rekey

    def describe(self):
        return " . ".join(self.names)


def chain_claims(mapper, classify=True):
    """``ChainSpec`` when the mapper chain is a certified jax-traceable
    numeric chain, else ``(None, reason)``.  Returns ``(spec, reason)``.

    ``classify=False`` skips the purity/determinism gate (callers that
    already ran :func:`props.stage_verdict`)."""
    from .. import base
    from ..plan import ir
    from . import props

    def _gate(f, kind):
        """Classify + certify one UDF; returns the reason or None."""
        if classify:
            v = props.classify_callable(f)
            if not v.pure:
                return "UDF {} impure: {}".format(
                    props.callable_name(f), "; ".join(v.impure_evidence))
            if not v.deterministic:
                return "UDF {} nondeterministic: {}".format(
                    props.callable_name(f), "; ".join(v.nondet_evidence))
        ok, why = certify_callable(f, kind)
        if not ok:
            return "UDF {} not traceable: {}".format(
                props.callable_name(f), why)
        return None

    ops = []
    names = []
    rekey = None
    for leaf in ir.flatten_mapper(mapper):
        if type(leaf) is base.Map and leaf.mapper is base._identity:
            continue
        if rekey is not None:
            return None, "op {} follows the re-key — only a TRAILING " \
                "Rekey certifies (records leave the value lane there)" \
                .format(type(leaf).__name__)
        if type(leaf) is base.ValueMap:
            kind = "map"
        elif type(leaf) is base.Filter:
            kind = "filter"
        elif type(leaf) is base.Rekey:
            # Trailing re-key (fold_by/count/a_group_by): the key fn —
            # and the value fn when present — certify as elementwise
            # numeric maps over the value lane, so (key_f(v),
            # value_f(v)) records build from two lanes of the same
            # program.
            why = _gate(leaf.key_f, "map")
            if why is not None:
                return None, "re-key " + why
            if leaf.value_f is not None:
                # "value" admits scalar outputs too (count()'s constant
                # ``lambda v: 1`` broadcasts over the lane).
                why = _gate(leaf.value_f, "value")
                if why is not None:
                    return None, "re-key value " + why
            rekey = (leaf.key_f, leaf.value_f)
            names.append("Rekey[{}]".format(
                props.callable_name(leaf.key_f)))
            continue
        else:
            return None, "op {} outside the certified lane vocabulary " \
                "(ValueMap/Filter + trailing Rekey)".format(
                    type(leaf).__name__)
        f = leaf.f
        why = _gate(f, kind)
        if why is not None:
            return None, why
        ops.append((kind, f))
        names.append("{}[{}]".format(type(leaf).__name__,
                                     props.callable_name(f)))
    if not ops and rekey is None:
        return None, "identity chain (nothing to lower)"
    return ChainSpec(ops, names, rekey=rekey), \
        "certified jax-traceable numeric chain: " + " . ".join(names)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def _pow2(n):
    return max(8, 1 << max(0, (n - 1).bit_length()))


class ChainProgram(object):
    """Executable form of a certified chain, with per-program counters
    (surfaced in stats / tests)."""

    def __init__(self, spec):
        self.spec = spec
        self._jits = {}  # (dtype str) -> jitted program
        self.counters = {"batches": 0, "device_dispatched": 0,
                         "device_verified": 0, "device_mismatch": 0,
                         "host_vectorized": 0, "fallback": 0,
                         "diff_checked": 0, "diff_diverged": 0}
        self._lock = threading.Lock()

    def count(self, key, n=1):
        """Locked counter bump: one cached program is shared by every
        concurrent map job of its stage, and ``+=`` is a lost-update
        race across threads (the counters are stats/test surface)."""
        with self._lock:
            self.counters[key] += n

    # -- host (authoritative) evaluation ------------------------------------
    def run_host(self, vals):
        """Vectorized 64-bit evaluation: ``(keys_or_None, out_vals,
        mask_or_None)``.  ``vals`` is a 1-D numeric numpy array; ``keys``
        is the re-key lane when the chain ends in a certified Rekey."""
        if vals.dtype.kind == "i":
            cur = vals.astype(np.int64, copy=False)
        else:
            cur = vals.astype(np.float64, copy=False)
        mask = None
        keys = None
        # divide/invalid RAISE: numpy would silently emit inf/nan where
        # the authoritative per-record Python path raises
        # ZeroDivisionError — the FloatingPointError lands in
        # run_batch's fallback except, so the batch re-runs per-record
        # and surfaces the genuine exception (byte-identity contract).
        # Overflow/underflow stay IEEE-silent, matching Python floats.
        with np.errstate(divide="raise", invalid="raise",
                         over="ignore", under="ignore"):
            for kind, f in self.spec.ops:
                out = np.asarray(f(cur)) if kind == "map" else None
                if kind == "map":
                    cur = out
                else:
                    m = np.asarray(f(cur))
                    m = m if m.dtype == bool else (m != 0)
                    mask = m if mask is None else (mask & m)
            if self.spec.rekey is not None:
                key_f, value_f = self.spec.rekey
                keys = np.asarray(key_f(cur))
                if value_f is not None:
                    cur = np.asarray(value_f(cur))
                    if cur.ndim == 0:  # constant value fn (count())
                        cur = np.broadcast_to(cur, keys.shape).copy()
        return keys, cur, mask

    # -- device dispatch -----------------------------------------------------
    def _jit_for(self, dtype):
        key = str(dtype)
        fn = self._jits.get(key)
        if fn is None:
            import jax

            ops = self.spec.ops
            rekey = self.spec.rekey

            def numeric_chain(lane):
                cur = lane
                mask = None
                for kind, f in ops:
                    if kind == "map":
                        cur = f(cur)
                    else:
                        m = f(cur)
                        m = m.astype(bool) if m.dtype != bool else m
                        mask = m if mask is None else mask & m
                import jax.numpy as jnp

                if mask is None:
                    mask = jnp.ones(lane.shape, dtype=bool)
                keys = None
                if rekey is not None:
                    key_f, value_f = rekey
                    keys = key_f(cur)
                    if value_f is not None:
                        cur = jnp.broadcast_to(jnp.asarray(value_f(cur)),
                                               keys.shape)
                return keys, cur, mask

            fn = jax.jit(numeric_chain)
            with self._lock:
                self._jits[key] = fn
        return fn

    def _device_dtype(self, vals):
        """The dtype the device program computes in, or None when no
        exact dispatch exists for this lane under the current backend."""
        import jax

        x64 = jax.config.jax_enable_x64
        k = vals.dtype.kind
        if k == "i":
            if x64:
                return np.dtype(np.int64)
            if len(vals) and (vals.max() > _INT32_MAX
                              or vals.min() < _INT32_MIN):
                return None
            return np.dtype(np.int32)
        if k == "f":
            # 32-bit float compute rounds differently from the 64-bit
            # host authority: verification would fail every block.
            return np.dtype(np.float64) if x64 else None
        return None

    def run_batch(self, ks, vs):
        """Execute the chain over one record batch (parallel Python
        lists — the batched-UDF protocol).  Returns ``(keys_out,
        values_out)`` as plain Python lists with the filter mask
        applied, or None when the batch is outside the vectorized
        contract (non-numeric lane, a UDF that rejects array input,
        non-elementwise output) — the caller falls back to the
        per-record path, which is always authoritative."""
        try:
            vals = np.asarray(vs)
        except Exception:  # noqa: BLE001 - mixed/unconvertible values
            self.count("fallback")
            return None
        if vals.ndim != 1 or vals.dtype.name not in _LANE_DTYPES \
                or vals.dtype.hasobject:
            self.count("fallback")
            return None
        try:
            host_keys, host_vals, mask = self.run_host(vals)
            host_vals = np.asarray(host_vals)
        except Exception:  # noqa: BLE001 - the UDF rejected the lane form
            self.count("fallback")
            return None
        if host_vals.ndim != 1 or len(host_vals) != len(vals) \
                or host_vals.dtype.hasobject:
            self.count("fallback")
            return None
        if self.spec.rekey is not None and (
                host_keys is None or host_keys.ndim != 1
                or len(host_keys) != len(vals)
                or host_keys.dtype.hasobject):
            self.count("fallback")
            return None
        self.count("batches")
        ddt = self._device_dtype(vals) if (
            settings.use_device and settings.use_device_for(len(vals))) \
            else None
        if ddt is not None:
            try:
                self._dispatch_and_verify(vals, ddt, host_keys,
                                          host_vals, mask)
            except Exception as e:  # noqa: BLE001 - host result stands
                self.count("device_mismatch")
                log.debug("device chain dispatch failed (%s); host "
                          "vectorized result stands", e)
        else:
            self.count("host_vectorized")
        out_vals = host_vals.tolist()
        out_ks = (host_keys.tolist() if host_keys is not None
                  else list(ks))
        if mask is None:
            return out_ks, out_vals
        keep = mask.tolist()
        return (list(itertools.compress(out_ks, keep)),
                list(itertools.compress(out_vals, keep)))

    def _dispatch_and_verify(self, vals, ddt, host_keys, host_vals,
                             mask):
        from ..obs import trace as _trace
        from ..ops import devtime

        n = len(vals)
        n_pad = _pow2(n)
        lane = vals.astype(ddt, copy=False)
        if n_pad != n:
            lane = np.pad(lane, (0, n_pad - n), mode="edge")
        fn = self._jit_for(ddt)
        with _trace.span("device", "numeric-chain", records=n):
            with devtime.track("device"):
                okeys, out, omask = fn(lane)
                out = np.asarray(out)[:n]
                omask = np.asarray(omask)[:n]
                if okeys is not None:
                    okeys = np.asarray(okeys)[:n]
        self.count("device_dispatched")
        hmask = (np.ones(n, dtype=bool) if mask is None else mask)

        def _up(a, ref):
            return a.astype(np.int64 if ref.dtype.kind == "i"
                            else np.float64)

        verified = (np.array_equal(omask, hmask) and np.array_equal(
            _up(out, host_vals)[hmask], host_vals[hmask]))
        if verified and host_keys is not None:
            verified = okeys is not None and np.array_equal(
                _up(okeys, host_keys)[hmask], host_keys[hmask])
        if verified:
            self.count("device_verified")
        else:
            self.count("device_mismatch")
            log.debug("device chain result mismatched the 64-bit host "
                      "evaluation; host result stands (exactness gate)")


import collections

#: Chain-identity -> ChainProgram.  Stage nodes are slotted (no weakrefs)
#: so programs key on the ordered (kind, id(f)) chain identity; each
#: entry holds strong refs to its UDFs (via the spec), which keeps the
#: ids valid for exactly as long as the entry lives.  LRU-bounded: a
#: long-lived session constructing fresh lambdas per run can't grow it
#: without bound, and an evicted entry only costs a re-jit.
_PROGRAMS = collections.OrderedDict()
_PROGRAMS_CAP = 256
_PROG_LOCK = threading.Lock()


def _chain_key(spec):
    """Cache key for one certified chain.  The trailing re-key is part
    of the program identity: two bare ``fold_by``/``count`` chains have
    identical (empty) lane ops but different key/value functions — an
    ops-only key would hand the second stage the first one's compiled
    program."""
    key = tuple((kind, id(f)) for kind, f in spec.ops)
    if spec.rekey is not None:
        key_f, value_f = spec.rekey
        key += (("rekey", id(key_f),
                 id(value_f) if value_f is not None else None),)
    return key


def stage_program(stage):
    """Cached :class:`ChainProgram` for a certified stage (None when the
    stage's chain does not certify — the runner re-checks so a stale
    ``exec_target`` annotation can never dispatch an unknown op)."""
    spec, _why = chain_claims(stage.mapper)
    if spec is None:
        return None
    key = _chain_key(spec)
    with _PROG_LOCK:
        prog = _PROGRAMS.get(key)
        if prog is None:
            prog = ChainProgram(spec)
            _PROGRAMS[key] = prog
        else:
            _PROGRAMS.move_to_end(key)
        while len(_PROGRAMS) > _PROGRAMS_CAP:
            _PROGRAMS.popitem(last=False)
    return prog
