"""Plain float32 reference of the synchronous ACSP-FL round.

Written from the paper's description (ACSP-FL, 10.1016/j.adhoc.2024.103462:
har-mlp, local SGD, DLD layer sharing, masked partial FedAvg, distributed
evaluation, accuracy filter with decay) and from the wire format the
configuration names, with no import from the program under test. It makes
its own initial weights from the seed and reads only the generated data.

Each round is *teacher-forced*: it trains the clients that the candidate
run selected, at the share depths the candidate run used. Selection and
DLD are discrete decisions; ``decision_errors`` checks them separately, as
laws applied to the candidate's own accuracies, so a rounding flip of one
test sample cannot send the two trajectories apart.

``precision`` is the matmul precision: ``"highest"`` (float32) for the
reference, ``"high"`` (three bfloat16 passes, emulated explicitly so that
it reads the same on any backend) for the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QMAX = {"int8": 127.0}


# --- matmul at a stated precision -------------------------------------------

def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm3_raw(a, b):
    """float32 product from three bfloat16 passes (hi*hi + hi*lo + lo*hi)."""
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.custom_vjp
def _mm3(a, b):
    return _mm3_raw(a, b)


def _mm3_fwd(a, b):
    return _mm3_raw(a, b), (a, b)


def _mm3_bwd(res, g):
    a, b = res
    return _mm3_raw(g, b.T), _mm3_raw(a.T, g)


_mm3.defvjp(_mm3_fwd, _mm3_bwd)


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        return _mm3(a, b)
    raise ValueError(f"unknown precision {precision!r}")


# --- the model: har-mlp ------------------------------------------------------

def init_params(seed: int, sizes):
    """He-normal weights and zero biases; the paper's init, keyed as the
    configuration states: PRNGKey(seed) -> (init, loop); one split per layer."""
    r_init, r_loop = jax.random.split(jax.random.PRNGKey(seed))
    rng = r_init
    params = []
    for fan_in, fan_out in zip(sizes[:-1], sizes[1:]):
        rng, sub = jax.random.split(rng)
        w = jax.random.normal(sub, (fan_in, fan_out), jnp.float32) * jnp.sqrt(2.0 / fan_in)
        params.append({"w": w, "b": jnp.zeros((fan_out,), jnp.float32)})
    return params, r_loop


def logits(params, x, precision):
    h = x
    for i, layer in enumerate(params):
        h = matmul(h, layer["w"], precision) + layer["b"]
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return h


def loss(params, x, y, m, precision, half_batch=False):
    logp = jax.nn.log_softmax(logits(params, x, precision), axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    m = m.astype(jnp.float32)
    if half_batch:  # fault: the second half of each batch left out
        m = m * (jnp.arange(m.shape[0]) < m.shape[0] // 2)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def accuracy(params, x, y, m, precision):
    pred = jnp.argmax(logits(params, x, precision), axis=-1)
    m = m.astype(jnp.float32)
    return jnp.sum((pred == y) * m) / jnp.maximum(jnp.sum(m), 1.0)


# --- one client's local training (Algorithm 2) -------------------------------

def local_sgd(params, x, y, m, *, epochs, batch, lr, precision, half_batch=False):
    """``epochs`` passes of minibatch SGD over the whole batches of the slab
    (the tail that does not fill a batch is not trained: remainder "drop")."""
    nb = max(1, x.shape[0] // batch)
    xb = x[: nb * batch].reshape(nb, batch, -1)
    yb = y[: nb * batch].reshape(nb, batch)
    mb = m[: nb * batch].reshape(nb, batch)
    grad = jax.grad(loss)

    def step(p, b):
        g = grad(p, *b, precision, half_batch)
        return jax.tree.map(lambda a, d: a - lr * d, p, g), None

    for _ in range(epochs):
        params, _ = jax.lax.scan(step, params, (xb, yb, mb))
    return params


# --- the wire codec: per-block absmax int8, stochastic rounding --------------

def int8_roundtrip(x, key, block=512):
    flat = x.reshape(-1)
    n = flat.shape[0]
    bp = min(block, max(n, 8))
    nb = -(-n // bp)
    u = jax.random.uniform(key, (n,))
    pad = nb * bp - n
    xb = jnp.pad(flat, (0, pad)).reshape(nb, bp)
    ub = jnp.pad(u, (0, pad)).reshape(nb, bp)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=1), 1e-12) / QMAX["int8"]
    q = jnp.clip(jnp.floor(xb / scale[:, None] + ub), -QMAX["int8"], QMAX["int8"])
    return (q * scale[:, None]).reshape(-1)[:n].reshape(x.shape)


def _layer_roundtrip(layer, key):
    """Encode and decode each leaf of one layer; leaf keys fold the leaf's
    index in name order ("b" before "w")."""
    return {
        name: int8_roundtrip(layer[name], jax.random.fold_in(key, i))
        for i, name in enumerate(sorted(layer))
    }


# --- one federated round -----------------------------------------------------

def _where_lanes(mask, new, old):
    return jax.tree.map(
        lambda n, o: jnp.where(mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, old
    )


@functools.partial(
    jax.jit,
    static_argnames=("epochs", "batch", "lr", "codec", "precision", "fault"),
)
def round_step(state, sel, pms, data, *, epochs, batch, lr, codec, precision, fault=None):
    """One synchronous round on every client lane, teacher-forced on ``sel``
    (C,) and ``pms`` (C,). Returns the new state and (accuracy, update_norm)."""
    g, local, residual, rng, prev_norm = state
    x_tr, y_tr, m_tr, x_te, y_te, m_te = data
    c = sel.shape[0]
    n_layers = len(g)
    share = jnp.arange(n_layers)[None, :] < pms[:, None]  # (C, L)
    if codec == "int8":
        rng, _, _, r_codec = jax.random.split(rng, 4)
    else:
        rng, _, _ = jax.random.split(rng, 3)

    # personalization: shared layers from the global model, the rest local
    def compose(glob, loc):
        return [
            jax.tree.map(
                lambda gl, lo, j=j: jnp.where(
                    share[:, j].reshape((-1,) + (1,) * (lo.ndim - 1)), gl[None], lo
                ),
                glob[j], loc[j],
            )
            for j in range(n_layers)
        ]

    train_model = compose(g, local)
    if fault == "unchanged":  # fault: local training returns its input
        trained = train_model
    else:
        trained = jax.vmap(
            lambda p, x, y, m: local_sgd(
                p, x, y, m, epochs=epochs, batch=batch, lr=lr, precision=precision,
                half_batch=fault == "half_batch",
            )
        )(train_model, x_tr, y_tr, m_tr)

    # transmit: what the server receives of each layer, and the residuals
    if codec == "int8":
        received, new_residual = [], []
        for j in range(n_layers):
            keys = jax.random.split(jax.random.fold_in(r_codec, j), c)
            comp = jax.tree.map(lambda t, gl, r: t - gl[None] + r, trained[j], g[j], residual[j])
            dec = jax.vmap(_layer_roundtrip)(comp, keys)
            received.append(jax.tree.map(lambda gl, d: gl[None] + d, g[j], dec))
            sent = sel & share[:, j]
            new_residual.append(
                _where_lanes(sent, jax.tree.map(lambda a, d: a - d, comp, dec), residual[j])
            )
    else:
        received, new_residual = trained, residual

    norm_sq = jnp.zeros((c,), jnp.float32)
    for j in range(n_layers):
        for name in received[j]:
            d = received[j][name] - g[j][name][None]
            norm_sq = norm_sq + share[:, j] * jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
    norm = jnp.sqrt(norm_sq)

    # finite guard: a non-finite update is not aggregated and changes nothing
    ok = jnp.isfinite(norm)
    sel_ok = sel & ok
    if new_residual is not None:
        new_residual = _where_lanes(ok, new_residual, residual)
    norm = jnp.where(ok, norm, prev_norm)
    new_local = _where_lanes(sel_ok, trained, local)

    # masked partial FedAvg, weighted by each client's train sample count
    n_samples = jnp.sum(m_tr, axis=1).astype(jnp.float32)
    new_g = []
    for j in range(n_layers):
        w = sel_ok * share[:, j] * n_samples
        total = jnp.sum(w)
        new_g.append(
            jax.tree.map(
                lambda r, gl: jnp.where(
                    total > 0,
                    jnp.sum(r * w.reshape((-1,) + (1,) * (r.ndim - 1)), axis=0)
                    / jnp.maximum(total, 1e-12),
                    gl,
                ),
                received[j], g[j],
            )
        )

    # distributed evaluation of each client's composed model
    eval_model = compose(new_g, new_local)
    acc = jax.vmap(lambda p, x, y, m: accuracy(p, x, y, m, precision))(
        eval_model, x_te, y_te, m_te
    )
    if fault == "answer":  # fault: client 0's answer altered where produced
        acc = acc.at[0].set(1.0 - acc[0])
    return (new_g, new_local, new_residual, rng, norm), (acc, norm)


# --- the decision laws (ACSP-FL selection, Eq. 4-7; DLD, Eq. 9) --------------

def acsp_select(acc: np.ndarray, t: int, decay: float, tol: float = 0.0) -> np.ndarray:
    """Clients whose accuracy is at most the mean, the ``ceil(|S|(1-decay)^t)``
    worst of them (ties by client id). ``tol`` shifts the mean and the kept
    count by that much, to bound what float32 rounding can decide."""
    a = np.asarray(acc, np.float32)
    filtered = a <= np.float32(a.mean(dtype=np.float64) + tol)
    kept = int(np.ceil(filtered.sum() * (1.0 - decay) ** t - tol))
    keyed = np.where(filtered, a, np.inf)
    order = np.argsort(keyed, kind="stable")
    sel = np.zeros(a.shape, bool)
    sel[order[: min(kept, int(filtered.sum()))]] = True
    return sel


def dld_layers(acc: np.ndarray, n_layers: int, tol: float = 0.0) -> np.ndarray:
    a = np.asarray(acc, np.float64)
    inv = 1.0 / np.maximum(a, 1e-6) + tol
    pms = np.where(a <= 0.25, n_layers, np.ceil(inv))
    return np.clip(pms, 1, n_layers).astype(np.int32)


def decision_errors(acc, sel, pms, decay, n_layers, tol=1e-5) -> int:
    """Lanes, over rounds 1.., whose selection or share depth differs from
    both laws applied to the previous round's accuracies with the mean, the
    kept count and 1/accuracy moved by +-``tol`` (float32 rounding order is
    not part of the law). Round 0 selects everyone at full depth."""
    errors = int((~sel[0]).sum()) + int((pms[0] != n_layers).sum())
    for t in range(1, acc.shape[0]):
        lo = acsp_select(acc[t - 1], t - 1, decay, -tol)
        hi = acsp_select(acc[t - 1], t - 1, decay, tol)
        errors += int(((sel[t] != lo) & (sel[t] != hi)).sum())
        lo = dld_layers(acc[t - 1], n_layers, -tol)
        hi = dld_layers(acc[t - 1], n_layers, tol)
        errors += int(((pms[t] != lo) & (pms[t] != hi)).sum())
    return errors


# --- the reference run -------------------------------------------------------

class Run:
    """The reference's state from the seed; ``step`` runs one round."""

    def __init__(self, data, seed, recipe, sizes, precision="highest", fault=None):
        g, rng = init_params(seed, sizes)
        c = data.x_train.shape[0]
        local = jax.tree.map(lambda a: jnp.broadcast_to(a, (c,) + a.shape), g)
        residual = (
            jax.tree.map(jnp.zeros_like, local) if recipe["codec"] == "int8" else None
        )
        self.state = (g, local, residual, rng, jnp.zeros((c,), jnp.float32))
        self.arrays = tuple(jnp.asarray(a) for a in (
            data.x_train, data.y_train, data.m_train,
            data.x_test, data.y_test, data.m_test,
        ))
        self.kw = dict(
            epochs=recipe["epochs"], batch=recipe["batch_size"], lr=recipe["lr"],
            codec=recipe["codec"], precision=precision, fault=fault,
        )

    def step(self, sel, pms):
        self.state, (acc, norm) = round_step(
            self.state, jnp.asarray(sel), jnp.asarray(pms, jnp.int32), self.arrays, **self.kw
        )
        return np.asarray(acc), np.asarray(norm)


def run(data, seed, recipe, sizes, sel, pms, precision="highest", fault=None):
    """Rounds ``0..len(sel)-1``, teacher-forced on ``sel``/``pms`` (T, C).
    Returns (accuracy, update_norm), each (T, C) numpy."""
    ref = Run(data, seed, recipe, sizes, precision, fault)
    out = [ref.step(s, p) for s, p in zip(sel, pms)]
    return np.stack([a for a, _ in out]), np.stack([n for _, n in out])


def run_free(data, seed, recipe, sizes, rounds, precision="high", fault=None):
    """The reference put in the program's place: it makes its own decisions
    by the laws. Returns the candidate outputs (acc, sel, pms, update_norm),
    each (rounds, C)."""
    c = data.x_train.shape[0]
    n_layers = len(sizes) - 1
    ref = Run(data, seed, recipe, sizes, precision, fault)
    sel, pms = np.ones((c,), bool), np.full((c,), n_layers, np.int32)
    out = {"acc": [], "sel": [], "pms": [], "norm": []}
    for t in range(rounds):
        acc, norm = ref.step(sel, pms)
        for key, value in zip(out, (acc, sel, pms, norm)):
            out[key].append(value)
        sel, pms = acsp_select(acc, t, recipe["decay"]), dld_layers(acc, n_layers)
    return tuple(np.stack(v) for v in out.values())


# --- buffered asynchronous aggregation (FedBuff-style events) ----------------

@functools.partial(
    jax.jit, static_argnames=("epochs", "batch", "lr", "precision", "exponent", "fault")
)
def event_step(g, snap, local, prev_norm, land, stale, pms, data, *,
               epochs, batch, lr, precision, exponent, fault=None):
    """One aggregation event: every landing client (``land`` (C,)) trains
    from the global model it was dispatched with (``snap``, leaves
    (C, ...)), composed with its local layers at its dispatch depth
    ``pms``; the server merges the float32 deltas weighted by sample count
    times the staleness discount ``(1 + stale)^-exponent``; every client is
    evaluated on its composed model. Returns (g, local, norm, acc)."""
    x_tr, y_tr, m_tr, x_te, y_te, m_te = data
    n_layers = len(g)
    share = jnp.arange(n_layers)[None, :] < pms[:, None]

    def compose(glob, loc, stacked):
        return [
            jax.tree.map(
                lambda gl, lo, j=j: jnp.where(
                    share[:, j].reshape((-1,) + (1,) * (lo.ndim - 1)),
                    gl if stacked else gl[None], lo,
                ),
                glob[j], loc[j],
            )
            for j in range(n_layers)
        ]

    train_model = compose(snap, local, True)
    if fault == "unchanged":
        trained = train_model
    else:
        trained = jax.vmap(
            lambda p, x, y, m: local_sgd(p, x, y, m, epochs=epochs, batch=batch, lr=lr,
                                         precision=precision,
                                         half_batch=fault == "half_batch")
        )(train_model, x_tr, y_tr, m_tr)
    delta = [jax.tree.map(lambda t, s: t - s, trained[j], snap[j]) for j in range(n_layers)]
    norm_sq = jnp.zeros(land.shape, jnp.float32)
    for j in range(n_layers):
        for d in delta[j].values():
            norm_sq = norm_sq + share[:, j] * jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
    norm = jnp.sqrt(norm_sq)
    landed = land & jnp.isfinite(norm)
    n_samples = jnp.sum(m_tr, axis=1).astype(jnp.float32)
    weight = landed * n_samples * (1.0 + stale.astype(jnp.float32)) ** (-exponent)
    new_g = []
    for j in range(n_layers):
        w = weight * share[:, j]
        total = jnp.sum(w)
        new_g.append(jax.tree.map(
            lambda d, gl: gl + jnp.where(
                total > 0,
                jnp.sum(d * w.reshape((-1,) + (1,) * (d.ndim - 1)), axis=0)
                / jnp.maximum(total, 1e-12),
                0.0,
            ),
            delta[j], g[j],
        ))
    local = _where_lanes(landed, trained, local)
    norm = jnp.where(landed, norm, prev_norm)
    acc = jax.vmap(lambda p, x, y, m: accuracy(p, x, y, m, precision))(
        compose(new_g, local, False), x_te, y_te, m_te
    )
    if fault == "answer":
        acc = acc.at[0].set(1.0 - acc[0])
    return new_g, local, norm, acc


def dispatch_law(acc, t, decay, n_layers, idle, free_slots, landers, tol):
    """The clients the laws dispatch after event ``t`` (ascending ids among
    those selection wants and that are idle, as many as there are free
    slots; the landers themselves when no one else is in flight and no one
    is wanted), and the share depth each is dispatched at."""
    out = []
    for sign in (-1.0, 1.0):
        want = acsp_select(acc, t, decay, sign * tol) & idle
        n = min(int(want.sum()), free_slots)
        ids = np.nonzero(want)[0][:n]
        if n == 0 and free_slots == len(idle):
            ids = np.asarray(sorted(landers))
        out.append((set(ids.tolist()), dld_layers(acc, n_layers, sign * tol)))
    return out


def run_async(data, seed, recipe, sizes, outs, decisions, slots, precision="highest",
              fault=None):
    """Events ``0..T-1`` (T = rows of ``outs``), teacher-forced on the
    candidate's landings and dispatches (``decisions``, in the order the
    hooks saw them). Returns (accuracy, update_norm, decision_errors).

    The control and the planted faults of an async cell are this function
    at ``precision="high"`` or with ``fault`` set, following the program's
    own event schedule (which the simulated clock fixes, not the values)."""
    g, _ = init_params(seed, sizes)
    c = data.x_train.shape[0]
    n_layers = len(sizes) - 1
    arrays = tuple(jnp.asarray(a) for a in (
        data.x_train, data.y_train, data.m_train, data.x_test, data.y_test, data.m_test
    ))
    local = jax.tree.map(lambda a: jnp.broadcast_to(a, (c,) + a.shape), g)
    versions = [g]
    dver = np.zeros((c,), np.int64)
    pms = np.full((c,), n_layers, np.int32)
    in_flight = np.zeros((c,), bool)
    norm = jnp.zeros((c,), jnp.float32)
    errors, accs, norms = 0, [], []
    t = -1
    acc_prog = np.asarray(outs["acc"])
    landers = np.zeros((0,), np.int64)
    for d in decisions:
        if d[0] == "dispatch":
            ids, client_pms = d[1], d[2]
            if t < 0:  # warm start: the first ``slots`` clients at full depth
                errors += len(set(ids.tolist()) ^ set(range(slots)))
                errors += int((client_pms != n_layers).sum())
            else:
                idle = ~in_flight
                free = slots - int(in_flight.sum())
                laws = dispatch_law(acc_prog[t], t, recipe["decay"], n_layers, idle,
                                    free, landers.tolist(), 1e-5)
                if not any(set(ids.tolist()) == want for want, _ in laws):
                    errors += len(set(ids.tolist()) ^ laws[0][0])
                ok_pms = [(client_pms[ids] == p[ids]) for _, p in laws]
                errors += int((~(ok_pms[0] | ok_pms[1])).sum())
            dver[ids] = t + 1
            pms = np.asarray(client_pms, np.int32)
            in_flight[ids] = True
            continue
        t += 1
        if t >= acc_prog.shape[0]:
            break
        landers = np.asarray(d[1])
        land = np.zeros((c,), bool)
        land[landers] = True
        stale = np.where(land, t - dver, 0)
        snap = jax.tree.map(lambda *v: jnp.stack(v)[jnp.asarray(dver)], *versions)
        g, local, norm, acc = event_step(
            g, snap, local, norm, jnp.asarray(land), jnp.asarray(stale, jnp.int32),
            jnp.asarray(pms), arrays, epochs=recipe["epochs"], batch=recipe["batch_size"],
            lr=recipe["lr"], precision=precision, exponent=recipe["staleness_exponent"],
            fault=fault,
        )
        versions.append(g)
        in_flight[landers] = False
        accs.append(np.asarray(acc))
        norms.append(np.where(land, np.asarray(norm), np.asarray(outs["norm"][t])))
    return np.stack(accs), np.stack(norms), errors
