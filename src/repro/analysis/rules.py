"""The initial rule set — each rule pins one repo-level invariant.

  no-dense-silo-stack   the server never materializes / reduces an
                        (n, d, d) decompressed silo stack (PR 3's
                        guarantee, generalized to every method x
                        compressor combination)
  no-dense-roundtrip    the Pallas payload path never builds a
                        block^2-trailing-dim dense selection mask or
                        scatter round-trip (PR 4's guarantee, promoted
                        from tests/test_infra.py)
  dtype-discipline      under x64 no f64 value is silently downcast and
                        then laundered back into an f64 result (or into
                        the program output)
  no-host-sync          no io/pure/debug callback inside a jitted hot
                        path (host round-trips serialize the step)
  padding-sentinel      every drop-mode scatter fed by a payload index
                        stream remaps -1 before the scatter (jax
                        normalizes negatives to index n-1 BEFORE the
                        bounds check — unremapped padding silently
                        overwrites the last row)
  vmem-budget           every pallas_call's per-program block footprint
                        (sum of BlockSpec tiles x dtype width) fits the
                        VMEM dispatch budget — fail at trace time, not
                        as a runtime OOM

All rules are trace-only: they walk jaxprs, never execute them.
"""

from __future__ import annotations

import numpy as np

from .framework import Rule, Target, register_rule
from .jaxpr_utils import (
    PALLAS_PRIMITIVE,
    describe_eqn,
    dtype_of,
    is_literal,
    producer_map,
    shape_of,
    walk_eqns,
    walk_jaxprs,
)

_REDUCING = ("reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
             "reduce_and", "reduce_or", "reduce_precision")


@register_rule
class NoDenseSiloStack(Rule):
    """No dense (n, d, d) silo stack on the server path.

    On ``aggregate`` targets (a ``Compressor.aggregate`` trace over
    stacked payloads): no equation may *emit* an (n, d, d) array at all
    — the structure-aware fast paths go straight from payload space to
    ONE dense accumulator. Dense-wire families (Identity, Natural,
    Dithering — payload already carries one slot per entry, marked
    ``wire_is_dense``) are exempted by the target builder, not here.

    On every other kind (method-step, precond): device-side (n, d, d)
    arrays are legitimate (stacked Hessian oracles, per-silo H_i
    state, per-silo diffs entering compress), so the rule instead
    flags any *reduction* of an (n, d, d) input into a (d, d) output —
    the decompress-then-mean server aggregation the payload pipeline
    exists to delete.
    """

    name = "no-dense-silo-stack"
    description = ("server aggregation stays in payload space: no "
                   "(n, d, d) decompressed stack is built or reduced")

    def check(self, jaxpr, target: Target):
        n = target.context.get("silo_axis")
        dense = tuple(target.context.get("dense_shape", ()))
        if not n or not dense:
            return []
        stack = (int(n),) + dense
        out = []
        for eqn, in_pallas in walk_eqns(jaxpr):
            if in_pallas or eqn.primitive.name == PALLAS_PRIMITIVE:
                continue
            if target.kind != "aggregate":
                if (eqn.primitive.name in _REDUCING
                        or eqn.primitive.name == "dot_general"):
                    if any(shape_of(v) == stack for v in eqn.invars
                           if not is_literal(v)) and any(
                               shape_of(v) == dense for v in eqn.outvars):
                        out.append(self.violation(
                            target,
                            f"dense reduction of the {stack} silo stack "
                            f"into {dense} — server aggregation must stay "
                            "in payload space",
                            describe_eqn(eqn)))
            else:
                for v in eqn.outvars:
                    if shape_of(v) == stack:
                        out.append(self.violation(
                            target,
                            f"materializes the dense {stack} silo stack "
                            "(decompress-then-mean path)",
                            describe_eqn(eqn)))
        return out


@register_rule
class NoDenseRoundtrip(Rule):
    """No intermediate with a block^2 trailing dim outside pallas_call
    bodies — neither the dense per-tile selection mask nor the dense
    scatter round-trip exists in the traced step (in-kernel tiles are
    VMEM-resident by construction and exempt)."""

    name = "no-dense-roundtrip"
    description = ("the payload compression path never materializes a "
                   "block^2-trailing-dim dense tile intermediate outside "
                   "kernel bodies")

    def check(self, jaxpr, target: Target):
        block = int(target.context.get("block", 0))
        # ``dense_forbidden``: an exact shape (e.g. the full (d, d) diff
        # a fused diff->select->payload kernel keeps out of HBM) that
        # must not appear as any equation output outside kernel bodies.
        # Separate from ``block`` because fused-uplink targets have
        # legitimate (d, d)-shaped *inputs* but may never rebuild the
        # dense difference as an intermediate.
        forbidden = tuple(target.context.get("dense_forbidden", ()))
        if not block and not forbidden:
            return []
        bb = block * block
        out = []
        for eqn, in_pallas in walk_eqns(jaxpr):
            if in_pallas or eqn.primitive.name == PALLAS_PRIMITIVE:
                continue
            for v in eqn.outvars:
                shape = shape_of(v)
                if block and shape and shape[-1] == bb:
                    out.append(self.violation(
                        target,
                        f"dense block^2={bb} trailing-dim intermediate "
                        "(selection mask / per-tile scatter round-trip)",
                        describe_eqn(eqn)))
                elif forbidden and shape == forbidden:
                    out.append(self.violation(
                        target,
                        f"dense {forbidden} intermediate on a fused "
                        "diff->payload path (the difference must stay "
                        "tile-resident inside the kernel)",
                        describe_eqn(eqn)))
        return out


_NARROW_FLOATS = ("float32", "float16", "bfloat16")


@register_rule
class DtypeDiscipline(Rule):
    """No silent f64 -> narrow-float downcast that re-enters an f64
    result. Under x64 the paper's accounting is double precision end to
    end; a narrowing ``convert_element_type`` is only a bug when the
    narrowed value flows back into f64 (precision laundering) or into
    the program output — narrowing used purely for *selection* (index
    computation, comparisons) is documented behavior and passes because
    the taint dies at the bool/int boundary.

    Scope: per-jaxpr dataflow (taint does not cross scan/jit
    boundaries; the downcast and its re-entry live in the same traced
    scope in every pattern this repo contains)."""

    name = "dtype-discipline"
    description = ("no silent f64->f32 downcast on the Hessian path "
                   "re-entering an f64 result under x64")

    def check(self, jaxpr, target: Target):
        out = []
        for scope, in_pallas in walk_jaxprs(jaxpr):
            if in_pallas:
                continue
            out.extend(self._check_scope(scope, target,
                                         outermost=scope is getattr(
                                             jaxpr, "jaxpr", jaxpr)))
        return out

    def _check_scope(self, scope, target: Target, outermost: bool):
        tainted = set()
        out = []
        for eqn in scope.eqns:
            if eqn.primitive.name == PALLAS_PRIMITIVE:
                continue
            in_tainted = any(not is_literal(v) and v in tainted
                             for v in eqn.invars)
            if eqn.primitive.name == "convert_element_type":
                src = dtype_of(eqn.invars[0])
                dst = dtype_of(eqn.outvars[0])
                src_name = getattr(src, "name", "")
                dst_name = getattr(dst, "name", "")
                if src_name == "float64" and dst_name in _NARROW_FLOATS:
                    tainted.add(eqn.outvars[0])
                    continue
                if dst_name == "float64" and in_tainted:
                    out.append(self.violation(
                        target,
                        "f64 value silently downcast and converted back "
                        "to f64 (precision laundering)",
                        describe_eqn(eqn)))
                    continue
            if in_tainted:
                for v in eqn.outvars:
                    name = getattr(dtype_of(v), "name", "")
                    if name in _NARROW_FLOATS:
                        tainted.add(v)
        if outermost:
            for v in scope.outvars:
                if not is_literal(v) and v in tainted:
                    out.append(self.violation(
                        target,
                        "program output is an f64 value silently "
                        "downcast to "
                        f"{getattr(dtype_of(v), 'name', '?')}",
                        f"outvar {getattr(dtype_of(v), 'name', '?')}"
                        f"{list(shape_of(v))}"))
        return out


_CALLBACKS = ("pure_callback", "io_callback", "debug_callback",
              "debug_print", "outside_call")


@register_rule
class NoHostSync(Rule):
    """No host callback primitive inside a jitted hot path: every
    callback forces a device->host->device round trip that serializes
    the step (and breaks multi-host execution)."""

    name = "no-host-sync"
    description = ("no io_callback/pure_callback/debug_callback inside "
                   "jitted hot paths")

    def check(self, jaxpr, target: Target):
        out = []
        for eqn, _ in walk_eqns(jaxpr):
            if eqn.primitive.name in _CALLBACKS:
                out.append(self.violation(
                    target,
                    f"host callback `{eqn.primitive.name}` inside a "
                    "jitted hot path",
                    describe_eqn(eqn)))
        return out


def _mode_is_drop(mode) -> bool:
    return "FILL_OR_DROP" in str(mode)


class _Slicer:
    """Backward slice over index dataflow, following values across
    jit/scan/cond scope boundaries where the mapping is positional."""

    TRANSPARENT = ("reshape", "broadcast_in_dim", "convert_element_type",
                   "squeeze", "expand_dims", "transpose", "slice", "rev",
                   "copy", "stop_gradient", "gather", "dynamic_slice")
    SAFE_SOURCES = ("iota", "top_k", "argsort", "sort", "argmax", "argmin",
                    "cumsum", "cumprod", "cummax", "cummin", "rng_bit_generator")
    SANITIZERS = ("clamp",)
    COMBINING = ("add", "sub", "mul", "div", "rem", "neg", "concatenate",
                 "pad", "select_and_scatter_add", "min")

    def __init__(self):
        self.seen = set()

    def safe(self, var, frames) -> bool:
        """frames: list of (jaxpr, parent_frames_entry) from outermost in
        — each entry is (scope_jaxpr, producing_eqn_in_parent or None).
        Returns True when ``var`` provably cannot carry an unremapped
        negative payload index into the scatter."""
        if is_literal(var):
            return True
        key = id(var)
        if key in self.seen:
            return True  # cycle/diamond: already being verified
        self.seen.add(key)

        scope, parent = frames[-1]
        if var in getattr(scope, "constvars", ()):
            return True  # trace-time constant
        if var in scope.invars:
            if parent is None:
                return False  # the traced program's own input: a raw
                # payload index stream may be negative
            outer_eqn, outer_frames = parent
            mapped = self._map_invar(scope, var, outer_eqn)
            if mapped is None:
                return True  # unmapped scope boundary: inconclusive
            return self.safe(mapped, outer_frames)

        prod = self.producers(scope).get(var)
        if prod is None:
            return True
        name = prod.primitive.name
        if name in self.SAFE_SOURCES:
            return True
        if name in self.SANITIZERS:
            return True
        if name == "max":
            # max(i, c) with a non-negative constant clamps the padding
            ops = prod.invars
            if any(is_literal(o) and np.all(np.asarray(o.val) >= 0)
                   for o in ops):
                return True
            return all(self.safe(o, frames) for o in ops)
        if name == "select_n":
            return self._select_safe(prod, frames)
        if name in self.TRANSPARENT:
            return self.safe(prod.invars[0], frames)
        if name in self.COMBINING:
            return all(self.safe(o, frames) for o in prod.invars)
        if name in ("jit", "closed_call", "core_call", "scan", "while",
                    "cond", "custom_jvp_call", "custom_vjp_call"):
            return True  # opaque producer: inconclusive, do not flag
        if name.startswith("scatter"):
            # indices built by a scatter (payload *construction*): the
            # fill value may be -1 by design — treat as unsafe only if
            # its own inputs are unsafe is overly deep; inconclusive
            return True
        if name.startswith("random_") or "random" in name:
            return True
        return False  # unknown producer of an index stream

    def _select_safe(self, eqn, frames) -> bool:
        """A ``select_n`` guarding the index stream. jnp auto-inserts
        the negative-wrap normalization ``select(i < 0, i, i + n)`` at
        every indexing site — that pattern is TRANSPARENT (the hazard:
        -1 wraps to n-1). Any *other* select (e.g. the explicit
        ``where(i < 0, n, i)`` remap, whose negative branch does not
        derive from i) is a sanitizer."""
        pred, on_false, on_true = eqn.invars[0], eqn.invars[1], eqn.invars[2]
        scope, _ = frames[-1]
        prods = self.producers(scope)
        pred_eqn = None if is_literal(pred) else prods.get(pred)
        if pred_eqn is not None and pred_eqn.primitive.name == "lt":
            compared = pred_eqn.invars[0]
            true_eqn = None if is_literal(on_true) else prods.get(on_true)
            if (true_eqn is not None
                    and true_eqn.primitive.name == "add"
                    and any((not is_literal(o)) and o is compared
                            for o in true_eqn.invars)):
                # auto-normalization: keep slicing from the raw index
                return self.safe(compared, frames)
        return True  # a user-level remap/guard: sanitized

    def _map_invar(self, scope, var, eqn):
        """Map a sub-jaxpr invar back to the producing eqn's operand
        (positional for jit/closed_call and scan; None elsewhere)."""
        idx = list(scope.invars).index(var)
        name = eqn.primitive.name
        if name in ("jit", "closed_call", "core_call", "scan"):
            if idx < len(eqn.invars):
                return eqn.invars[idx]
        return None

    def producers(self, scope) -> dict:
        cache = getattr(scope, "_analysis_producers", None)
        if cache is None:
            cache = producer_map(scope)
            try:
                object.__setattr__(scope, "_analysis_producers", cache)
            except (AttributeError, TypeError):
                pass
        return cache


@register_rule
class PaddingSentinel(Rule):
    """Every drop-mode scatter whose index stream may contain ``-1``
    payload padding must remap the sentinel out of range *before* the
    scatter: jax normalizes negative indices (-1 -> n-1) ahead of the
    ``mode='drop'`` bounds check, so unremapped padding silently
    overwrites the last slot instead of being dropped. Detected
    statically: a FILL_OR_DROP scatter whose backward index slice
    reaches a program input (a payload index stream) through jnp's
    negative-wrap normalization with no sanitizing remap in between."""

    name = "padding-sentinel"
    description = ("-1 payload padding is remapped out of range before "
                   "every mode='drop' scatter")

    def check(self, jaxpr, target: Target):
        out = []
        self._walk(getattr(jaxpr, "jaxpr", jaxpr), None, out, target)
        return out

    def _walk(self, scope, parent, out, target, in_pallas=False):
        from .jaxpr_utils import _as_jaxpr, subjaxprs

        scope = _as_jaxpr(scope)
        frames_here = (parent[1] + [(scope, parent)]) if parent \
            else [(scope, None)]
        for eqn in scope.eqns:
            is_pallas = eqn.primitive.name == PALLAS_PRIMITIVE
            if (not in_pallas and not is_pallas
                    and eqn.primitive.name.startswith("scatter")
                    and _mode_is_drop(eqn.params.get("mode"))):
                idx_var = eqn.invars[1]
                if not _Slicer().safe(idx_var, frames_here):
                    out.append(self.violation(
                        target,
                        "drop-mode scatter consumes a potentially "
                        "negative payload index stream without "
                        "remapping -1 out of range first (negative "
                        "indices wrap to n-1 BEFORE the bounds check)",
                        describe_eqn(eqn)))
            for sub in subjaxprs(eqn):
                self._walk(sub, (eqn, frames_here), out, target,
                           in_pallas or is_pallas)


@register_rule
class VmemBudget(Rule):
    """Every ``pallas_call``'s per-program VMEM block footprint — the
    sum over its BlockSpecs of tile-elements x dtype width (operand
    tiles + output/accumulator tiles) — must fit the dispatch budget
    (``repro.kernels.VMEM_BUDGET_BYTES``, 8 MiB of the ~16 MiB/core
    VMEM, leaving headroom for scratch and double buffering). Checked
    statically from the traced grid mapping, so an over-budget kernel
    config fails analysis instead of OOMing on device."""

    name = "vmem-budget"
    description = ("pallas_call BlockSpec footprints fit the 8 MiB VMEM "
                   "dispatch budget at trace time")

    def check(self, jaxpr, target: Target):
        from ..kernels import VMEM_BUDGET_BYTES

        budget = int(target.context.get("vmem_budget", VMEM_BUDGET_BYTES))
        out = []
        for eqn, _ in walk_eqns(jaxpr):
            if eqn.primitive.name != PALLAS_PRIMITIVE:
                continue
            gm = eqn.params.get("grid_mapping")
            if gm is None:
                continue
            total = 0
            parts = []
            for bm in gm.block_mappings:
                shape = tuple(int(s) for s in bm.block_aval.shape)
                dtype = np.dtype(bm.array_aval.dtype)
                total += int(np.prod(shape)) * dtype.itemsize
                parts.append(f"{shape}x{dtype.name}")
            if total > budget:
                kname = getattr(eqn.params.get("name_and_src_info"),
                                "name", "pallas_call")
                out.append(self.violation(
                    target,
                    f"kernel `{kname}` blocks {' + '.join(parts)} = "
                    f"{total / 2**20:.1f} MiB exceed the "
                    f"{budget / 2**20:.0f} MiB VMEM dispatch budget",
                    describe_eqn(eqn)))
        return out
