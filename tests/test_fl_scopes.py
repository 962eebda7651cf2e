"""Named scopes on the round step's device ops (``repro.fl.phases``): every
phase of the sync round step, the fused chunk, the async step and the
sharded step appears in the compiled program's ``op_name`` metadata, so a
device trace can attribute each op to its phase. The numbers the steps
compute are guarded unchanged by the golden and bit-identity tests."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_federated_classification
from repro.fl import FLConfig, api, phases
from repro.fl.sched import AsyncState, _setup_run, build_async_step
from repro.models.mlp import mlp_accuracy, mlp_loss

from _subproc import run_forced

PHASES = ("fl.personalize", "fl.train", "fl.transmit", "fl.aggregate", "fl.eval",
          "fl.select", "fl.gather", "fl.scatter")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def small_ds():
    return make_federated_classification(
        n_clients=8, n_classes=4, n_features=20,
        samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
        client_shift=0.05, class_sep=5.0, seed=1,
    )


def scopes_in(hlo_text: str) -> set[str]:
    """Every ``fl.`` scope named in a compiled program's op_name metadata."""
    return {s for name in OP_NAME.findall(hlo_text) for s in re.findall(r"fl\.[a-z]+", name)}


def _setup(ds, **kw):
    cfg = FLConfig(rounds=4, epochs=1, codec="int8", personalization="dld", **kw)
    return cfg, _setup_run(ds, cfg, None, mlp_loss, mlp_accuracy, None, None, None)


@pytest.mark.parametrize("kind", ["round", "chunk", "sharded", "lane-local"])
def test_sync_steps_name_every_phase(small_ds, kind):
    """The sharded step gathers its cohort and scatters it back; with one
    client a device (``lane-local``) the client's slabs stay where its lane
    runs, so that step has no scatter."""
    if kind == "lane-local":  # one client on the one device
        small_ds = make_federated_classification(
            n_clients=1, n_classes=4, n_features=20, samples_per_client_range=(60, 90),
            dirichlet_alpha=50.0, client_shift=0.05, class_sep=5.0, seed=1,
        )
    cfg, su = _setup(small_ds, cohort_devices=1 if kind in ("sharded", "lane-local") else 0)
    step = api.build_round_step(su.env, su.pipeline, cfg.execution)
    state = su.initial_state()
    if kind == "chunk":
        lowered = api.build_chunk_step(step, 2).lower(state, jnp.arange(2, dtype=jnp.int32))
    else:
        lowered = jax.jit(step).lower(state, jnp.asarray(0))
    found = scopes_in(lowered.compile().as_text())
    expected = set(PHASES) - ({"fl.scatter"} if kind == "lane-local" else set())
    assert expected <= found, expected - found
    assert "fl.round" in found
    assert ("fl.chunk" in found) == (kind == "chunk")


def test_async_step_names_every_phase(small_ds):
    cfg, su = _setup(small_ds, scheduler="async", buffer_k=2)
    c, m = small_ds.n_clients, small_ds.n_clients
    state = AsyncState(
        global_params=su.g0,
        slot_params=jax.tree.map(lambda g: jnp.broadcast_to(g, (m,) + g.shape), su.g0),
        slot_client=jnp.arange(m, dtype=jnp.int32),
        slot_pms=jnp.full((m,), su.pms0, jnp.int32),
        client_pms=jnp.full((c,), su.pms0, jnp.int32),
        local_params=su.loc0,
        accuracy=jnp.zeros((c,), jnp.float32),
        loss=jnp.zeros((c,), jnp.float32),
        update_norm=jnp.zeros((c,), jnp.float32),
        rng=su.r_loop,
        residual=su.residual0,
        participation=jnp.zeros((c,), jnp.int32),
    )
    land = np.zeros((m,), bool)
    land[:2] = True
    args = (state, jnp.asarray(0), jnp.asarray(land), jnp.zeros((m,), jnp.int32),
            jnp.ones((m,), bool), jnp.asarray(land), jnp.asarray(False))
    step = jax.jit(build_async_step(su.env, su.pipeline))
    found = scopes_in(step.lower(*args).compile().as_text())
    assert set(PHASES) <= found, set(PHASES) - found
    assert "fl.event" in found and "fl.round" not in found


def test_every_subclass_entry_method_is_scoped():
    """A phase subclass defined outside the module gets its scope too, and
    every phase class's entry methods carry theirs."""

    class Halver(phases.Aggregator):
        def aggregate(self, ctx, env):
            return ctx._replace(new_global=jax.tree.map(lambda g: g / 2, ctx.global_params))

    assert Halver.aggregate.fl_scope == "fl.aggregate"
    ctx = phases.RoundContext(global_params=[jnp.ones(3)])
    text = jax.jit(lambda c: Halver().aggregate(c, None).new_global).lower(ctx).as_text(
        debug_info=True)
    assert "fl.aggregate/div" in text
    for cls, method, scope in [
        (phases.ComposePersonalizer, "eval_model", "fl.personalize"),
        (phases.Personalizer, "local_fallback", "fl.personalize"),
        (phases.SGDTrainer, "fit", "fl.train"),
        (phases.TransmitPhase, "wire_costs", "fl.transmit"),
        (phases.StalenessAggregator, "aggregate", "fl.aggregate"),
        (phases.DistributedEvaluator, "evaluate", "fl.eval"),
        (phases.SelectorPhase, "select", "fl.select"),
        (phases.DLDPolicy, "next_pms", "fl.select"),
    ]:
        assert getattr(cls, method).fl_scope == scope, (cls, method)


_SHARDED_BODY = """
import re, jax, jax.numpy as jnp
from repro.data.synthetic import make_federated_classification
from repro.fl import FLConfig, api
from repro.fl.sched import _setup_run
from repro.models.mlp import mlp_accuracy, mlp_loss
from test_fl_scopes import PHASES, OP_NAME, scopes_in

ds = make_federated_classification(
    n_clients=8, n_classes=4, n_features=20,
    samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
    client_shift=0.05, class_sep=5.0, seed=1,
)
cfg = FLConfig(rounds=4, epochs=1, personalization="dld", cohort_devices=2)
su = _setup_run(ds, cfg, None, mlp_loss, mlp_accuracy, None, None, None)
step = api.build_round_step(su.env, su.pipeline, cfg.execution)
chunk = api.build_chunk_step(step, 2)
text = chunk.lower(su.initial_state(), jnp.arange(2, dtype=jnp.int32)).compile().as_text()
assert set(PHASES) <= scopes_in(text), set(PHASES) - scopes_in(text)
kinds = {}
for line in text.splitlines():
    m = re.search(r" (all-gather|all-reduce)\\(", line)
    if m:
        kinds.setdefault(m.group(1), set()).add(re.findall(r"fl\\.[a-z]+", OP_NAME.search(line).group(1))[-1])
# the aggregation psum is in fl.aggregate; the lanes' all-gather is in
# fl.scatter (the cohort mask's, for the executed lane, in fl.gather)
assert kinds["all-reduce"] == {"fl.aggregate"}, kinds
gathers = kinds["all-gather"]
assert "fl.scatter" in gathers and gathers <= {"fl.gather", "fl.scatter"}, kinds
print("SCOPES OK", kinds)
"""


@pytest.mark.multidevice
def test_sharded_step_scopes_its_collectives_d2():
    assert "SCOPES OK" in run_forced(_SHARDED_BODY, n_devices=2)
