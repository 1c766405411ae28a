"""Known-bad shard-audit fixtures (tests/test_static_analysis.py).

A miniature mesh-aware entry-point registry with >=2 seeded violations
per DTL15x checker family, paired with fx_shard_contract.json. Loaded by
FILE PATH through ``lint.trace.audit._load_registry`` exactly like the
real registry; every jit here is a few-op toy over a 2-device ("x",)
host mesh so the whole fixture audit runs in seconds.

Seeded violations (pinned in TestShard):

* DTL151 — ``fx.noisy`` lowers two shard_map all-reduces against a
  contract budget of one; ``fx.unlisted`` lowers a collective-permute
  the contract does not list at all; ``fx.sneaky`` is over budget like
  fx.noisy but inline-suppressed on its def line (the escape hatch)
* DTL152 — ``fx.drifted`` declares an expected P("x") arg sharding its
  jit is NOT lowered with (the ``:lowered`` code-level drift that
  --emit-contract cannot clear); ``fx.stale_contract`` matches its own
  lowering but the committed contract entry carries a doctored digest
  and param-spec map (the ``:contract`` drift that re-emitting clears)
* DTL153 — ``fx.replicated`` declares two rule-sharded parameter
  intents whose lowered arguments are fully replicated
* DTL154 — ``fx.resharder`` carries two in-program
  with_sharding_constraint sites against a budget of zero,
  ``fx.resharder2`` three against a budget of one
* DTL155 — ``fx.uncommitted`` is registered here but absent from the
  contract; ``fx.ghost`` exists only in the contract
* ``fx.clean`` (lowered) and ``fx.partitioned`` (compiled on the mesh,
  with the one GSPMD all-reduce its contracted-dim matmul implies)
  match their contract entries exactly and must stay finding-free.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lint.shard.audit import hlo_sharding_str
from lint.shard.types import ShardEntry

_PATH = "tests/fixtures_lint/fx_shard_registry.py"
_SDS = jax.ShapeDtypeStruct
_F8 = _SDS((8,), jnp.float32)
_F88 = _SDS((8, 8), jnp.float32)


def _mesh():
    return Mesh(np.asarray(jax.devices()[:2]), ("x",))


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _noisy(x):
    mesh = _mesh()
    f = _shard_map(lambda a: jax.lax.psum(jax.lax.psum(a, "x"), "x"),
                   mesh, P("x"), P())
    return f(x)


def _sneaky(x):  # dtl: disable=DTL151
    mesh = _mesh()
    f = _shard_map(lambda a: jax.lax.psum(jax.lax.psum(a, "x"), "x"),
                   mesh, P("x"), P())
    return f(x)


def _unlisted(x):
    mesh = _mesh()
    f = _shard_map(
        lambda a: jax.lax.ppermute(a, "x", [(0, 1), (1, 0)]),
        mesh, P("x"), P("x"),
    )
    return f(x)


def _plain(x):
    return x * 2.0


def _two_args(w1, w2):
    return w1 + w2


def _resharder(x):
    mesh = _mesh()
    y = jax.lax.with_sharding_constraint(
        x * 2, NamedSharding(mesh, P("x")))
    z = jax.lax.with_sharding_constraint(
        y + 1, NamedSharding(mesh, P()))
    return z


def _resharder2(x):
    mesh = _mesh()
    y = jax.lax.with_sharding_constraint(
        x * 2, NamedSharding(mesh, P("x")))
    z = jax.lax.with_sharding_constraint(
        y + 1, NamedSharding(mesh, P()))
    w = jax.lax.with_sharding_constraint(
        z * 3, NamedSharding(mesh, P("x")))
    return w


def _matmul(a, b):
    return a @ b


def _hlo(spec, ndim):
    return hlo_sharding_str(NamedSharding(_mesh(), spec), ndim)


def _jit_lower(fn, args, in_specs=None, out_specs=None):
    mesh = _mesh()
    kw = {}
    if in_specs is not None:
        kw["in_shardings"] = tuple(
            NamedSharding(mesh, s) for s in in_specs
        )
    if out_specs is not None:
        # every fixture jit returns ONE array; PartitionSpec is itself a
        # tuple subclass, so never iterate it
        kw["out_shardings"] = NamedSharding(mesh, out_specs)
    return jax.jit(fn, **kw).lower(*args)


def _ep(name, symbol, lower, **kw):
    return ShardEntry(
        name=name, path=_PATH, symbol=symbol, mesh_axes={"x": 2},
        lower=lower, **kw,
    )


def build_entry_points():
    return [
        _ep("fx.clean", "_plain",
            lambda: _jit_lower(_plain, (_F8,))),
        _ep("fx.noisy", "_noisy",
            lambda: _jit_lower(_noisy, (_F8,), in_specs=(P("x"),),
                               out_specs=P())),
        _ep("fx.sneaky", "_sneaky",
            lambda: _jit_lower(_sneaky, (_F8,), in_specs=(P("x"),),
                               out_specs=P())),
        _ep("fx.unlisted", "_unlisted",
            lambda: _jit_lower(_unlisted, (_F8,), in_specs=(P("x"),),
                               out_specs=P("x"))),
        _ep("fx.drifted", "_plain",
            lambda: _jit_lower(_plain, (_F8,)),
            arg_paths=("[0]",),
            in_shardings=(_hlo(P("x"), 1),)),
        _ep("fx.stale_contract", "_plain",
            lambda: _jit_lower(_plain, (_F8,))),
        _ep("fx.replicated", "_two_args",
            lambda: _jit_lower(_two_args, (_F8, _F8)),
            param_intents=(
                {"path": "w1", "rule": r"w1$", "requested": P("x"),
                 "spec": P("x"), "intent_sharded": True, "sharded": True,
                 "arg": 0},
                {"path": "w2", "rule": r"w2$", "requested": P("x"),
                 "spec": P("x"), "intent_sharded": True, "sharded": True,
                 "arg": 1},
            )),
        _ep("fx.resharder", "_resharder",
            lambda: _jit_lower(_resharder, (_F8,))),
        _ep("fx.resharder2", "_resharder2",
            lambda: _jit_lower(_resharder2, (_F8,))),
        _ep("fx.partitioned", "_matmul",
            lambda: _jit_lower(_matmul, (_F88, _F88),
                               in_specs=(P(None, "x"), P("x", None)),
                               out_specs=P()),
            partitioned=True),
        _ep("fx.uncommitted", "_plain",
            lambda: _jit_lower(_plain, (_F8,))),
    ]
