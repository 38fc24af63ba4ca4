"""PG — Pallas kernel geometry checker.

Consumes the abstract-evaluation reports of
:mod:`paddle_tpu.analysis.kernel_geometry` (memoized per module in the
run's ``PackageIndex``): every ``pl.pallas_call`` site reduced to its grid,
BlockSpecs, index-map arities, operand ranks/dims/dtypes, scalar-prefetch
arity and per-grid-step VMEM footprint, with block sizes and grid extents
resolved through module constants, ``functools.partial`` bindings,
enclosing-call-site parameters and autotune candidate tuples.

A mis-ranked index map or an over-budget block config otherwise only
surfaces as a cryptic Mosaic lowering error (or a silent clamp) at first
dispatch on TPU hardware this project rarely gets to touch; these checks
fail the same geometry at lint time.

Codes:

- PG901  BlockSpec rank discipline — block-shape length, index-map return
         arity, operand rank, and out_shape/out_specs structure must agree,
         and the kernel signature must take one ref per in/out/scratch
- PG902  in-bounds proof — an index-map window provably escapes its operand
         at a grid corner; an intentional clamp must be named via
         ``# analysis: disable=PG902 <reason>``.  Symbolic-residue axes are
         reported ``unproven`` in the geometry API, never silently passed —
         but only concrete overruns become findings
- PG903  per-grid-step VMEM window footprint (ins + outs + scratch, every
         resolvable configuration incl. autotune candidates) exceeds the
         per-target budget (``--vmem-budget``, default 16 MiB/core: Mosaic's
         default scoped limit), or the site's own
         ``CompilerParams(vmem_limit_bytes=...)`` where it states one
- PG904  scalar-prefetch discipline — ``PrefetchScalarGridSpec`` arg counts
         vs kernel signature positions; prefetch refs indexed only by
         grid-derived values
- PG905  fallback lockstep — a ``pallas_enabled``-gated dispatch without a
         counted ``warn_fallback`` degradation path, or a public kernel
         entry in ``kernels/`` no fallback-wrapped caller covers (the
         contract PRs 4/16 established by hand)
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Set

from paddle_tpu.analysis.checkers._shared import attr_chain
from paddle_tpu.analysis.core import Checker, FileContext, Violation
from paddle_tpu.analysis.kernel_geometry import (
    ModuleGeometry,
    SiteEval,
    evaluate_module,
)

# calls a gate predicate may make and still count as trivial (no dispatch)
_PREDICATE_CALLS = {
    "pallas_enabled", "bool", "int", "len", "isinstance", "getattr",
    "hasattr", "min", "max",
}

_DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024  # bytes per core, v4/v5 class


def _simple_call_names(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            if chain:
                out.add(chain.split(".")[-1])
    return out


class PallasGeometryChecker(Checker):
    name = "pallas_geometry"
    codes = {
        "PG901": "BlockSpec rank discipline: block shape, index-map arity, "
                 "operand rank and out_shape/out_specs must agree",
        "PG902": "index-map window provably escapes the operand at a grid "
                 "corner (name intentional clamps via a reasoned suppression)",
        "PG903": "per-grid-step VMEM window footprint exceeds the per-target "
                 "budget",
        "PG904": "scalar-prefetch discipline: PrefetchScalarGridSpec arity vs "
                 "kernel signature; prefetch refs indexed by non-grid values",
        "PG905": "Pallas kernel without XLA fallback lockstep (gated dispatch "
                 "or public kernel entry lacking warn_fallback coverage)",
    }

    # overridable per-run (CLI --vmem-budget); attribute so all_checkers()'s
    # no-arg construction stays valid
    vmem_budget: int = _DEFAULT_VMEM_BUDGET

    def run(self, ctx: FileContext) -> List[Violation]:
        out: List[Violation] = []
        geom = self._geometry(ctx)
        for site in geom.sites:
            out.extend(self._check_arity(ctx, site))
            out.extend(self._check_bounds(ctx, site))
            out.extend(self._check_vmem(ctx, site))
            out.extend(self._check_prefetch(ctx, site))
        out.extend(self._check_fallback(ctx))
        return out

    # -- report acquisition ----------------------------------------------------
    def _geometry(self, ctx: FileContext) -> ModuleGeometry:
        index = ctx.project.index
        if index is not None:
            try:
                return index.kernel_geometry(ctx.path, ctx.tree)
            except KeyError:
                pass
        return evaluate_module(ctx.path, ctx.tree, index)

    def _v(self, ctx, code, lineno, msg) -> Violation:
        return Violation(
            path=ctx.path, line=max(1, lineno), col=0, code=code, message=msg
        )

    # -- PG901 (+ arity halves of PG904) ---------------------------------------
    def _check_arity(self, ctx: FileContext, site: SiteEval) -> List[Violation]:
        out: List[Violation] = []
        arity_code = "PG904" if site.prefetch_grid_spec else "PG901"
        for spec in site.in_specs + site.out_specs:
            where = f"{spec.kind}_spec[{spec.index}] of {site.kernel_name}"
            if spec.block_shape is not None and spec.ret_arity is not None:
                if len(spec.block_shape) != spec.ret_arity:
                    out.append(self._v(
                        ctx, "PG901", spec.lineno,
                        f"{where}: block shape has {len(spec.block_shape)} dims "
                        f"but its index map returns {spec.ret_arity}",
                    ))
                    continue
            if (
                spec.block_shape is not None
                and spec.operand_rank is not None
                and len(spec.block_shape) != spec.operand_rank
            ):
                out.append(self._v(
                    ctx, "PG901", spec.lineno,
                    f"{where}: block shape has {len(spec.block_shape)} dims but "
                    f"the operand has rank {spec.operand_rank}",
                ))
            if (
                spec.index_map is not None
                and site.grid_len is not None
                and spec.map_params
            ):
                expected = site.grid_len + site.num_scalar_prefetch
                if len(spec.map_params) != expected:
                    out.append(self._v(
                        ctx, arity_code, spec.lineno,
                        f"{where}: index map takes {len(spec.map_params)} "
                        f"args but grid rank {site.grid_len}"
                        + (
                            f" + {site.num_scalar_prefetch} scalar-prefetch"
                            if site.num_scalar_prefetch
                            else ""
                        )
                        + f" = {expected}",
                    ))
        if (
            site.out_specs_declared
            and site.n_out_shapes is not None
            and len(site.out_specs) != site.n_out_shapes
        ):
            out.append(self._v(
                ctx, "PG901", site.lineno,
                f"{site.kernel_name}: {len(site.out_specs)} out_specs but "
                f"{site.n_out_shapes} out_shape entries",
            ))
        if (
            site.kernel_params is not None
            and not site.has_vararg
            and site.in_specs
            and (site.out_specs_declared or site.n_out_shapes is not None)
        ):
            n_out = (
                len(site.out_specs)
                if site.out_specs_declared
                else (site.n_out_shapes or 0)
            )
            expected = (
                site.num_scalar_prefetch
                + len(site.in_specs)
                + n_out
                + site.n_scratch
            )
            if len(site.kernel_params) != expected:
                out.append(self._v(
                    ctx, arity_code, site.lineno,
                    f"kernel {site.kernel_name} takes {len(site.kernel_params)} "
                    f"refs but the call wires {expected} "
                    f"({site.num_scalar_prefetch} prefetch + "
                    f"{len(site.in_specs)} in + {n_out} out + "
                    f"{site.n_scratch} scratch)",
                ))
        return out

    # -- PG902 -----------------------------------------------------------------
    def _check_bounds(self, ctx: FileContext, site: SiteEval) -> List[Violation]:
        out: List[Violation] = []
        for proof in site.axis_proofs:
            if proof.status == "overrun":
                out.append(self._v(
                    ctx, "PG902", proof.lineno or site.lineno,
                    f"{site.kernel_name}: {proof.detail}",
                ))
        return out

    # -- PG903 -----------------------------------------------------------------
    def _check_vmem(self, ctx: FileContext, site: SiteEval) -> List[Violation]:
        out: List[Violation] = []
        budget = int(self.vmem_budget)
        limit = site.vmem_limit
        if limit is not None:
            # the site asks Mosaic for its own scoped limit: that is its budget
            if not limit.known:
                return out  # derived from runtime shapes: nothing to hold it to
            budget = min(limit.values)
        seen: Set[str] = set()
        for cfg in site.vmem_configs:
            b = cfg.bytes_per_step
            if not b.known:
                continue
            worst = min(b.values)  # every resolvable value must exceed
            if worst <= budget:
                continue
            binding = ", ".join(f"{k}={v}" for k, v in sorted(cfg.binding.items()))
            key = f"{worst}:{binding}"
            if key in seen:
                continue
            seen.add(key)
            out.append(self._v(
                ctx, "PG903", site.lineno,
                f"{site.kernel_name}: per-grid-step VMEM window is "
                f">= {worst} bytes (budget {budget})"
                + (f" under config {binding}" if binding else "")
                + (" [element widths partly assumed 1 byte]" if cfg.assumed_width else ""),
            ))
        return out

    # -- PG904 (indexing half) -------------------------------------------------
    def _check_prefetch(self, ctx: FileContext, site: SiteEval) -> List[Violation]:
        return [
            self._v(ctx, "PG904", lineno, f"{site.kernel_name}: {detail}")
            for lineno, detail in site.prefetch_indexing
        ]

    # -- PG905 -----------------------------------------------------------------
    def _check_fallback(self, ctx: FileContext) -> List[Violation]:
        out: List[Violation] = []
        # (a) gated dispatch without a counted degradation path, any module
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            names = _simple_call_names(node)
            if "pallas_enabled" not in names or "warn_fallback" in names:
                continue
            if not (names - _PREDICATE_CALLS):
                continue  # trivial gate predicate (returns a bool, no dispatch)
            out.append(self._v(
                ctx, "PG905", node.lineno,
                f"{node.name} gates on pallas_enabled but never registers the "
                f"XLA degradation via warn_fallback (fallback counter contract)",
            ))
        # (b) public kernel entries in kernels/ need a fallback-wrapped caller
        if "kernels" in Path(ctx.path).parts:
            out.extend(self._check_kernel_coverage(ctx))
        return out

    def _check_kernel_coverage(self, ctx: FileContext) -> List[Violation]:
        out: List[Violation] = []
        index = ctx.project.index
        covered: Set[str] = index.fallback_covered() if index is not None else set()
        # module-local transitive pallas_call lowering
        local_defs: Dict[str, ast.AST] = {}
        for node in ctx.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local_defs[node.name] = node
        lowers_direct = {
            name
            for name, fn in local_defs.items()
            if any(
                isinstance(c, ast.Call)
                and (attr_chain(c.func) or "").endswith("pallas_call")
                for c in ast.walk(fn)
            )
        }

        def lowers(name: str, seen: Set[str]) -> bool:
            if name in lowers_direct:
                return True
            if name in seen or name not in local_defs:
                return False
            seen.add(name)
            return any(
                lowers(n, seen)
                for n in _simple_call_names(local_defs[name])
                if n in local_defs
            )

        for name, fn in local_defs.items():
            if name.startswith("_") or not lowers(name, set()):
                continue
            if "warn_fallback" in _simple_call_names(fn):
                continue  # self-gating entry (counts its own degradation)
            if name in covered:
                continue
            out.append(self._v(
                ctx, "PG905", fn.lineno,
                f"public Pallas kernel entry {name} has no fallback-wrapped "
                f"caller (no warn_fallback coverage anywhere in the package) "
                f"— register an XLA fallback in lockstep",
            ))
        return out
