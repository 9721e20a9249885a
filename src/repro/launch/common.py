"""Shared launcher CLI setup: mesh-spec parsing + policy wiring, and the
persistent compilation cache the entry points turn on.

``train.py`` and ``serve.py`` used to duplicate this block — including a
bug where ``--mesh 4`` or ``--mesh axb`` crashed with a raw ``ValueError``
from ``int()``.  ``parse_mesh`` validates the spec and raises a clean,
actionable error; ``resolve_mesh_and_policy`` turns that into
``parser.error`` (usage + exit 2) when called from a CLI.
"""

from __future__ import annotations

import os

import jax

from repro.core.engine import policy_from_spec
from repro.launch.mesh import make_local_mesh, make_production_mesh

__all__ = [
    "CHECKOUT_CACHE_DIR",
    "enable_compile_cache",
    "MESH_SPEC_HELP",
    "parse_mesh",
    "add_mesh_argument",
    "resolve_mesh_and_policy",
]

# <checkout>/.jax_cache: a fixed path (JAX keys cache entries by it), listed
# in .gitignore
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache lives in
    ``CHECKOUT_CACHE_DIR``.  Entry points call this before their first
    compile (never at import, so the test suite runs with the cache off)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


MESH_SPEC_HELP = (
    "mesh spec: DATAxMODEL with two positive integers (e.g. 1x1, 2x4) "
    "or 'production'"
)


def parse_mesh(spec: str):
    """Build a mesh from a CLI spec.  Raises ``ValueError`` with the spec
    grammar on anything malformed — never a bare ``int()`` traceback."""
    spec = str(spec).strip()
    if not spec:
        raise ValueError(f"empty mesh spec ({MESH_SPEC_HELP})")
    if spec == "production":
        return make_production_mesh()
    parts = spec.lower().split("x")
    if len(parts) != 2 or not all(p.strip().isdigit() for p in parts):
        raise ValueError(f"malformed mesh spec {spec!r} ({MESH_SPEC_HELP})")
    data, model = (int(p) for p in parts)
    if data < 1 or model < 1:
        raise ValueError(
            f"mesh axes must be positive, got {data}x{model} "
            f"({MESH_SPEC_HELP})"
        )
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices; "
            f"{n} present ({MESH_SPEC_HELP})"
        )
    return make_local_mesh(data, model)


def add_mesh_argument(parser) -> None:
    """Attach the shared ``--mesh`` option to an argparse parser."""
    parser.add_argument("--mesh", default="1x1", help=MESH_SPEC_HELP)


def resolve_mesh_and_policy(args, parser=None):
    """(mesh, policy) from parsed ``--mesh``/``--policy`` args.  With a
    ``parser``, malformed specs exit via ``parser.error`` (clean usage
    message) instead of a traceback."""
    try:
        mesh = parse_mesh(args.mesh)
        policy = policy_from_spec(args.policy, distributed=mesh.size > 1)
    except ValueError as e:
        if parser is not None:
            parser.error(str(e))
        raise
    return mesh, policy
