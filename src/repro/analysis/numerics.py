"""Numerics-accumulation pass: bf16 inputs must accumulate in f32.

The cuDNN low-precision lesson (PAPER.md / PAPERS.md): a half-precision
GEMM is only convergence-safe if the MXU accumulates in f32.  The repo
enforces that by convention (`preferred_element_type=jnp.float32`
everywhere); this pass enforces it by lint:

  NM401  trace every registered candidate at bf16 (abstract values only,
         nothing executes) and walk the jaxpr — recursing into
         pallas_call / pjit / scan sub-jaxprs — asserting every
         ``dot_general`` whose operands are sub-f32 carries
         ``preferred_element_type=float32``
  NM403  in the same jaxprs, flag any f32 value downcast below f32 and
         then *accumulated* (fed to add / sub / mul / dot_general): a
         downcast before the final accumulation throws away the mantissa
         the f32 accumulator exists to keep.  The terminal
         ``astype(out_dtype)`` store is fine — its consumer is a store,
         not an arithmetic op.  A downcast feeding a ``dot_general``
         that itself carries ``preferred_element_type=float32`` is also
         fine: that is NM401's blessed mixed-precision pattern — a
         quantized MXU *operand* re-accumulated in f32 (the flash
         kernels' ``probs.astype(v.dtype)`` before the PV mix), not a
         lost accumulator.
  NM402  AST check over ``kernels/*.py``: every ``scratch_shapes`` entry
         (the VMEM accumulators) must be ``pltpu.VMEM(<shape>,
         jnp.float32)``

The dynamic complement — proving the *padding* regions can't leak into
the logical output — is the poison sanitizer in ``sanitize.py``
(NM404, ``lint --sanitize``).
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Sequence, Tuple

from .findings import Finding

__all__ = ["check_numerics", "lint_kernel_scratch", "run"]

# shapes to trace at: one aligned, one ragged cell from the contract grid
TRACE_SHAPES: Tuple[Tuple[int, int, int, int], ...] = (
    (256, 256, 256, 2),
    (96, 160, 224, 3),
)

_LOW_PRECISION = ("bfloat16", "float16")
_ACCUM_PRIMS = {"add", "add_any", "sub", "mul", "dot_general"}


def _subjaxprs(value):
    """Yield every Jaxpr reachable from one eqn param value."""
    # duck-type: anything with .eqns is a jaxpr, anything with .jaxpr wraps one
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _walk_jaxprs(jaxpr):
    """Yield every (sub)jaxpr, depth-first, starting at ``jaxpr``."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in _subjaxprs(value):
                yield from _walk_jaxprs(sub)


def _check_traced(fn, avals, where: str) -> List[Tuple[str, str]]:
    """Trace ``fn`` over abstract ``avals``; return (rule, detail) pairs."""
    import jax
    import jax.numpy as jnp

    problems: List[Tuple[str, str]] = []
    closed = jax.make_jaxpr(fn)(*avals)
    f32 = jnp.dtype("float32")
    for sub in _walk_jaxprs(closed.jaxpr):
        consumers: dict = {}
        for eqn in sub.eqns:
            for var in eqn.invars:
                if hasattr(var, "aval"):  # skip Literal
                    consumers.setdefault(id(var), []).append(eqn)
        for eqn in sub.eqns:
            prim = eqn.primitive.name
            if prim == "dot_general":
                in_dtype = eqn.invars[0].aval.dtype
                pet = eqn.params.get("preferred_element_type")
                if jnp.dtype(in_dtype).name in _LOW_PRECISION and (
                    pet is None or jnp.dtype(pet) != f32
                ):
                    problems.append(
                        (
                            "NM401",
                            f"{where}: dot_general on {jnp.dtype(in_dtype).name} "
                            f"operands with preferred_element_type="
                            f"{pet!r} (must be float32)",
                        )
                    )
            elif prim == "convert_element_type":
                src = eqn.invars[0]
                if not hasattr(src, "aval"):
                    continue
                new_dtype = eqn.params.get("new_dtype")
                if (
                    jnp.dtype(src.aval.dtype) == f32
                    and new_dtype is not None
                    and jnp.dtype(new_dtype).name in _LOW_PRECISION
                ):
                    out = eqn.outvars[0]
                    for user in consumers.get(id(out), []):
                        uname = user.primitive.name
                        if uname not in _ACCUM_PRIMS:
                            continue
                        if uname == "dot_general":
                            upet = user.params.get("preferred_element_type")
                            if upet is not None and jnp.dtype(upet) == f32:
                                # quantized MXU operand, f32 accumulation:
                                # the mixed-precision pattern NM401 blesses
                                continue
                        problems.append(
                            (
                                "NM403",
                                f"{where}: f32 value downcast to "
                                f"{jnp.dtype(new_dtype).name} then fed "
                                f"to {uname}: downcast "
                                "before accumulation",
                            )
                        )
                        break
    return problems


def check_numerics(
    shapes: Sequence[Tuple[int, int, int, int]] = TRACE_SHAPES,
    repo_root: Optional[str] = None,
) -> List[Finding]:
    """NM401/NM403 over every registered candidate traced at bf16."""
    import jax
    import jax.numpy as jnp

    from repro.core.candidates import CANDIDATES
    from repro.core.measure import operand_shapes
    from repro.core.opkey import GROUPED_OPS
    from repro.kernels.tiling import DEFAULT_CONFIG_KEY, config_key

    from .contracts import _candidate_location

    findings: List[Finding] = []
    dtype = jnp.bfloat16
    for name, cand in sorted(CANDIDATES.items()):
        if cand.dtypes is not None and "bfloat16" not in cand.dtypes:
            continue
        path, line = _candidate_location(cand, repo_root)
        for op in cand.ops:
            for m, n, k, g in shapes:
                gg = g if op in GROUPED_OPS else 1
                avals = tuple(
                    jax.ShapeDtypeStruct(s, dtype)
                    for s in operand_shapes(op, m, n, k, g=gg)
                )
                space = cand.config_space(m, n, k, dtype.dtype.itemsize)
                configs = [None] + ([tuple(space[0])] if space else [])
                for cfg in configs:
                    ck = DEFAULT_CONFIG_KEY if cfg is None else config_key(cfg)
                    where = f"{name}:{op}:{m}x{n}x{k}x{gg}:{ck}"
                    try:
                        problems = _check_traced(
                            lambda *xs, _c=cfg: cand.run(*xs, config=_c),
                            avals,
                            where,
                        )
                    except Exception as exc:  # trace failure = contract bug
                        findings.append(
                            Finding(
                                rule="NM401",
                                path=path,
                                line=line,
                                message=f"{where}: bf16 trace failed: {exc}",
                                context=f"numerics:{where}:trace",
                            )
                        )
                        continue
                    for rule, detail in problems:
                        findings.append(
                            Finding(
                                rule=rule,
                                path=path,
                                line=line,
                                message=detail,
                                context=f"numerics:{where}:{rule}",
                            )
                        )
    return findings


def lint_kernel_scratch(path: str, relpath: str, tree=None) -> List[Finding]:
    """NM402: every scratch_shapes entry in one kernel file must be an
    ``pltpu.VMEM(<shape>, jnp.float32)`` accumulator."""
    findings: List[Finding] = []
    if tree is None:
        with open(path) as fh:
            tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg != "scratch_shapes":
                continue
            elems = kw.value.elts if isinstance(
                kw.value, (ast.List, ast.Tuple)
            ) else [kw.value]
            for idx, elem in enumerate(elems):
                ok = False
                detail = ast.dump(elem)[:80]
                if isinstance(elem, ast.Call):
                    callee = ast.unparse(elem.func)
                    detail = ast.unparse(elem)
                    if callee.endswith("VMEM") and len(elem.args) >= 2:
                        dtype_src = ast.unparse(elem.args[1])
                        ok = dtype_src.endswith("float32")
                if not ok:
                    findings.append(
                        Finding(
                            rule="NM402",
                            path=relpath,
                            line=elem.lineno,
                            message=(
                                f"VMEM accumulator scratch is not float32: "
                                f"{detail}"
                            ),
                            context=f"scratch:{relpath}:{idx}",
                        )
                    )
    return findings


def _kernel_files(repo_root: str) -> List[Tuple[str, str]]:
    kdir = os.path.join(repo_root, "src", "repro", "kernels")
    out = []
    for fname in sorted(os.listdir(kdir)):
        if fname.endswith(".py"):
            out.append(
                (os.path.join(kdir, fname), f"src/repro/kernels/{fname}")
            )
    return out


def run(repo_root: Optional[str] = None, cache=None) -> List[Finding]:
    if repo_root is None:
        from .lint import _repo_root

        repo_root = _repo_root()
    findings: List[Finding] = []
    for path, relpath in _kernel_files(repo_root):
        tree = cache.parse(path)[1] if cache is not None else None
        findings.extend(lint_kernel_scratch(path, relpath, tree))
    findings.extend(check_numerics(repo_root=repo_root))
    return findings
