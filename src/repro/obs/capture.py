"""Real-model traffic capture: tap the model zoo, record int8 wire streams
(DESIGN.md §16).

Every BT/power number before this layer came from synthetic streams
(``benchmarks/datagen.py``).  This module records the *actual* traffic of
the model zoo — decode weight/KV streams (``repro.serve``), a train step's
gradient all-reduce payload (``repro.train``), MoE dispatch buffers
(``repro.models.moe``) and trained-LeNet conv kernels
(``repro.models.lenet``) — as int8 wire images (``repro.traffic.int8_view``)
ready for the existing measurement stack: ``TxPipeline`` /
``dse.evaluate_grid`` / ``noc.simulate`` / the §15 activity plane.

The hook contract mirrors ``repro.obs.probes`` exactly (zero cost when
uninstalled):

  * production modules call ``repro._obs_hooks.tap(kind, **payload)`` at
    fixed tap sites — one ``None`` test while no capture is active;
  * a :func:`capture` context installs this module's ``_Tap`` into
    ``repro._obs_hooks.TAP``; every firing fans out to all active
    :class:`CaptureSession`\\ s;
  * payloads may be jax arrays or pytrees.  A tap site inside a jitted
    function fires with *tracers* during tracing — the tap drops those
    payloads whole (no jax operation ever touches them), so every traced
    jaxpr is byte-identical whether capture is absent, installed, or
    active (``tests/test_capture.py`` pins this in a subprocess).  Real
    values are recorded by calling the tapped functions *eagerly* (the
    ``capture_*`` scenario drivers below), outside any measured path.

The tap vocabulary (kind -> scenario):

  =================  ===============  =====================================
  kind               scenario         fired by
  =================  ===============  =====================================
  serve.weights      serve_decode     ``serve.generate`` once before the
                                      decode loop (the multicast weight
                                      stream)
  serve.kv           serve_decode     ``serve.generate`` after each decode
                                      step (the new KV / SSM-state bytes)
  train.grads        train_allreduce  ``train.make_train_step`` after the
                                      gradients are computed
  moe.dispatch       moe_dispatch     ``models.forward`` under capture,
                                      once per expert layer: the layer's
                                      MoE input rows and their routing
  lenet.conv         lenet_conv       ``models.lenet.lenet_forward``
                                      (trained conv kernels + input batch)
  =================  ===============  =====================================

Each recorded stream fires a ``capture.stream`` probe event (bytes per
scenario/stream) so captures show up in ``obs.collect`` registries and
``bench --trace`` timelines.

A ``moe.dispatch`` firing also keeps its arrays where they are, as one
:class:`MoeRoute` per expert layer in ``CaptureSession.routes``: the rows
in the compute dtype and the routing, which the expert-parallel
all-to-all (``repro.noc.moe_dispatch_flows``) is built from.
"""

from __future__ import annotations

import dataclasses
import json
from contextlib import contextmanager
from typing import Optional, Sequence

import numpy as np

from repro import _obs_hooks

__all__ = [
    "TAP_SCENARIOS",
    "CapturedStream",
    "MoeRoute",
    "CaptureSession",
    "capture",
    "capture_serve_decode",
    "capture_train_step",
    "capture_moe_dispatch",
    "capture_lenet_conv",
    "save_session",
    "load_session",
]

# the canonical tap vocabulary: tap kind -> report scenario.  Unknown kinds
# capture under their own name (new tap sites degrade gracefully, like
# unknown probe kinds in repro.obs.probes).
TAP_SCENARIOS: dict[str, str] = {
    "serve.weights": "serve_decode",
    "serve.kv": "serve_decode",
    "train.grads": "train_allreduce",
    "moe.dispatch": "moe_dispatch",
    "lenet.conv": "lenet_conv",
}


# --------------------------------------------------------------------------
# captured streams and sessions
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CapturedStream:
    """One recorded int8 wire stream.

    ``data`` is the 1-D uint8 view of the tensor's symmetric int8 wire
    image (``repro.traffic.int8_view``) — exactly the bytes the link /
    NoC / DSE stack measures.
    """

    scenario: str
    name: str
    kind: str
    data: np.ndarray
    source_shape: tuple[int, ...]
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def num_bytes(self) -> int:
        return int(self.data.size)


@dataclasses.dataclass(frozen=True)
class MoeRoute:
    """One expert layer's dispatch as the forward routed it (arrays stay
    on their device): ``x`` the (T, d) MoE input rows in the compute
    dtype, ``expert_ids`` the (T, k) experts of each token, ``probs`` the
    (T, E) float32 router probabilities."""

    layer: int
    x: object
    expert_ids: object
    probs: object


def _int8_bytes(arr) -> np.ndarray:
    """A tensor's int8 wire image as 1-D uint8 (already-int8 data passes
    through unquantized — it IS its own wire image)."""
    a = np.asarray(arr)
    if a.dtype == np.uint8:
        return a.reshape(-1)
    if a.dtype == np.int8:
        return a.view(np.uint8).reshape(-1)
    from repro.traffic.ordering import int8_view

    return np.asarray(int8_view(arr)).view(np.uint8).reshape(-1)


def _tree_bytes(tree, min_ndim: int) -> tuple[np.ndarray, int]:
    """Concatenated int8 wire bytes of a pytree's float leaves."""
    import jax
    import jax.numpy as jnp

    leaves = [
        x
        for x in jax.tree.leaves(tree)
        if getattr(x, "ndim", None) is not None
        and x.ndim >= min_ndim
        and x.size
        and jnp.issubdtype(x.dtype, jnp.floating)
    ]
    if not leaves:
        return np.zeros(0, np.uint8), 0
    return np.concatenate([_int8_bytes(x) for x in leaves]), len(leaves)


class CaptureSession:
    """An ordered collection of captured streams, grouped by scenario.

    Sessions are what the :func:`capture` context yields; they convert to
    the measurement stack's native shapes via :meth:`packets` (one
    concatenated packet matrix) and :meth:`workload` (one
    ``repro.dse.Workload`` with each captured stream measured
    independently — no seam transitions between streams, so per-stream
    BT sums exactly to the scenario total).
    """

    def __init__(self, name: str = "capture") -> None:
        self.name = name
        self.streams: list[CapturedStream] = []
        self.routes: list[MoeRoute] = []  # moe.dispatch firings, in order

    # ---------------- recording ----------------

    def add(
        self, scenario: str, name: str, tensor, *, kind: str = "manual", **meta
    ) -> CapturedStream:
        """Quantize ``tensor`` to its int8 wire image and record it."""
        data = _int8_bytes(tensor)
        shape = tuple(int(d) for d in getattr(tensor, "shape", (data.size,)))
        s = self._add_bytes(scenario, name, data, shape, kind, meta)
        _obs_hooks.event(
            "capture.stream",
            tap=kind,
            scenario=scenario,
            stream=name,
            bytes=s.num_bytes,
        )
        return s

    def _add_bytes(
        self,
        scenario: str,
        name: str,
        data: np.ndarray,
        source_shape: tuple[int, ...],
        kind: str,
        meta: dict,
    ) -> CapturedStream:
        s = CapturedStream(
            scenario=scenario,
            name=name,
            kind=kind,
            data=np.ascontiguousarray(data, dtype=np.uint8).reshape(-1),
            source_shape=tuple(int(d) for d in source_shape),
            meta=dict(meta),
        )
        self.streams.append(s)
        return s

    # ---------------- inspection ----------------

    def scenarios(self) -> tuple[str, ...]:
        return tuple(dict.fromkeys(s.scenario for s in self.streams))

    def get(
        self, scenario: str, name: str | None = None
    ) -> list[CapturedStream]:
        return [
            s
            for s in self.streams
            if s.scenario == scenario and (name is None or s.name == name)
        ]

    def scenario_bytes(
        self, scenario: str, names: Sequence[str] | None = None
    ) -> np.ndarray:
        sel = [
            s
            for s in self.get(scenario)
            if names is None or s.name in names
        ]
        if not sel:
            return np.zeros(0, np.uint8)
        return np.concatenate([s.data for s in sel])

    # ---------------- conversion to the measurement stack ----------------

    def packets(
        self,
        scenario: str,
        elems: int = 64,
        *,
        names: Sequence[str] | None = None,
        owner: str | None = None,
        strict: bool = False,
    ):
        """The scenario's captured bytes as one (P, elems) packet matrix.

        ``strict=True`` raises a clear :class:`ValueError` naming ``owner``
        when the byte count is not flit-divisible (otherwise the tail is
        trimmed to whole packets, the NoC-flow convention)."""
        data = self.scenario_bytes(scenario, names)
        return _bytes_to_packets(
            data, elems, owner=owner or scenario, strict=strict
        )

    def workload(
        self,
        scenario: str,
        *,
        elems: int = 64,
        lanes: int = 16,
        names: Sequence[str] | None = None,
        owner: str | None = None,
        strict: bool = False,
    ):
        """The scenario as a ``repro.dse.Workload``: every captured stream
        becomes its own (P, elems) measurement stream (independent links,
        Table-I style — stream BT adds with no seam transitions)."""
        from repro.dse.evaluate import Workload

        label = owner or scenario
        sel = [
            s for s in self.get(scenario) if names is None or s.name in names
        ]
        if not sel:
            raise ValueError(
                f"{label}: no captured streams for scenario {scenario!r} "
                f"(captured: {list(self.scenarios()) or 'nothing'})"
            )
        pkts = tuple(
            _bytes_to_packets(
                s.data, elems, owner=f"{label}/{s.name}", strict=strict
            )
            for s in sel
        )
        return Workload(name=label, streams=pkts, lanes=lanes)


def _bytes_to_packets(
    data: np.ndarray, elems: int, *, owner: str, strict: bool
):
    import jax.numpy as jnp

    n = int(data.size)
    if strict and n % elems:
        raise ValueError(
            f"{owner}: captured stream carries {n} bytes, which is not "
            f"divisible into {elems}-byte packets ({n % elems} bytes left "
            f"over) — the config's dims are not flit-divisible; pad the "
            f"model dims or pick a LinkSpec whose packet size divides {n}"
        )
    p = n // elems
    if p == 0:
        raise ValueError(
            f"{owner}: captured only {n} bytes — smaller than one "
            f"{elems}-byte packet; capture more traffic or shrink the "
            f"packet size"
        )
    return jnp.asarray(data[: p * elems].reshape(p, elems))


# --------------------------------------------------------------------------
# the tap installed into repro._obs_hooks.TAP
# --------------------------------------------------------------------------


def _has_tracer(payload: dict) -> bool:
    import jax

    return any(
        isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(payload)
    )


def _extract(kind: str, payload: dict) -> list[tuple]:
    """(name, bytes, source_shape, meta) streams of one tap firing."""
    if kind == "serve.weights":
        data, n = _tree_bytes(payload["params"], 2)
        return [("weights", data, (int(data.size),), {"leaves": n})]
    if kind == "serve.kv":
        cache = payload["cache"]
        step = int(payload.get("step", 0))
        parts = []
        if "k" in cache:
            # decode_step already advanced pos: the new KV row is pos-1
            pos = max(int(np.asarray(cache["pos"])) - 1, 0)
            for key in ("k", "v"):
                parts.append(_int8_bytes(cache[key][:, :, pos]))
        for key in ("ssm", "ssm_trailing"):
            if key in cache:
                data, _ = _tree_bytes(cache[key], 2)
                parts.append(data)
        data = (
            np.concatenate(parts) if parts else np.zeros(0, np.uint8)
        )
        return [("kv", data, (int(data.size),), {"step": step})]
    if kind == "train.grads":
        data, n = _tree_bytes(payload["grads"], 1)
        return [("grads", data, (int(data.size),), {"leaves": n})]
    if kind == "moe.dispatch":
        x = payload["x"]
        return [
            (
                "x",
                _int8_bytes(x),
                tuple(int(d) for d in x.shape),  # (T, d)
                {"layer": int(payload["layer"]),
                 "experts": int(payload["probs"].shape[-1]),
                 "top_k": int(payload["expert_ids"].shape[-1])},
            )
        ]
    # generic: every array-valued payload entry becomes one stream
    # (lenet.conv and future tap kinds)
    out = []
    for name, arr in payload.items():
        if getattr(arr, "ndim", None) is None or not getattr(arr, "size", 0):
            continue
        out.append(
            (
                name,
                _int8_bytes(arr),
                tuple(int(d) for d in arr.shape),
                {},
            )
        )
    return out


class _Tap:
    """The multiplexer installed into ``repro._obs_hooks.TAP``."""

    def __init__(self) -> None:
        self.sessions: list[CaptureSession] = []

    def tap(self, kind: str, payload: dict) -> None:
        if _has_tracer(payload):
            return  # tracing pass: drop whole payload, touch nothing
        if kind == "moe.dispatch":
            route = MoeRoute(int(payload["layer"]), payload["x"],
                             payload["expert_ids"], payload["probs"])
            for sess in self.sessions:
                sess.routes.append(route)
        scenario = TAP_SCENARIOS.get(kind, kind)
        for name, data, shape, meta in _extract(kind, payload):
            for sess in self.sessions:
                sess._add_bytes(scenario, name, data, shape, kind, meta)
            _obs_hooks.event(
                "capture.stream",
                tap=kind,
                scenario=scenario,
                stream=name,
                bytes=int(data.size),
            )


_TAP = _Tap()


def _refresh() -> None:
    _obs_hooks.TAP = _TAP if _TAP.sessions else None


@contextmanager
def capture(session: CaptureSession | None = None):
    """Activate traffic capture for the with-body; yields the session.

    Nested ``capture()`` scopes all record every tap firing (each scope
    keeps its own streams).  Entering the first scope installs the tap —
    before that, tap sites are a ``None`` test and nothing else.
    """
    sess = CaptureSession() if session is None else session
    _TAP.sessions.append(sess)
    _refresh()
    try:
        yield sess
    finally:
        _TAP.sessions.remove(sess)
        _refresh()


# --------------------------------------------------------------------------
# scenario drivers (shared by tests and benchmarks/model_traffic.py)
# --------------------------------------------------------------------------


def train_batch(cfg, batch: int = 2, seq: int = 16, seed: int = 0) -> dict:
    """A family-aware random batch for ``make_train_step`` (the
    ``tests/test_models_smoke.py`` construction, shared here so every
    config can be driven through capture)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    tok = jax.random.randint(key, (batch, seq), 0, cfg.vocab)
    lab = jax.random.randint(key, (batch, seq), 0, cfg.vocab)
    out = {"tokens": tok, "labels": lab}
    if cfg.family in ("encdec", "audio"):
        out["frames"] = jax.random.normal(
            key, (batch, 8, cfg.d_model), jnp.float32
        )
    elif cfg.family == "vlm":
        out["patches"] = jax.random.normal(
            key, (batch, cfg.n_frontend_tokens, cfg.d_model)
        )
        out["labels"] = jnp.pad(
            lab, ((0, 0), (cfg.n_frontend_tokens, 0)), constant_values=-100
        )
    return out


def capture_serve_decode(
    cfg,
    *,
    batch: int = 2,
    prompt: int = 8,
    new_tokens: int = 4,
    seed: int = 0,
    session: CaptureSession | None = None,
) -> CaptureSession:
    """Run ``serve.generate`` under capture: records the multicast weight
    stream once plus one KV/state stream per decoded token."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_params
    from repro.serve.loop import generate

    key = jax.random.key(seed)
    params = init_params(cfg, key)
    prompts = jax.random.randint(key, (batch, prompt), 0, cfg.vocab)
    kw = {}
    if cfg.family in ("encdec", "audio"):
        kw["frames"] = jax.random.normal(
            key, (batch, 8, cfg.d_model), jnp.float32
        )
    elif cfg.family == "vlm":
        kw["inputs_embeds"] = jax.random.normal(
            key, (batch, cfg.n_frontend_tokens, cfg.d_model)
        )
    with capture(session) as sess:
        generate(params, cfg, prompts, new_tokens, **kw)
    return sess


def capture_train_step(
    cfg,
    *,
    batch: int = 2,
    seq: int = 16,
    seed: int = 0,
    session: CaptureSession | None = None,
) -> CaptureSession:
    """Run one EAGER train step under capture: the ``train.grads`` tap
    records the gradient all-reduce payload (jitted callers trace through
    the same tap at zero cost — tracers are dropped)."""
    from repro.models import init_params
    from repro.optim import AdamWConfig
    from repro.optim import init as opt_init
    from repro.train import make_train_step

    import jax

    params = init_params(cfg, jax.random.key(seed))
    opt = opt_init(params)
    step = make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=10))
    with capture(session) as sess:
        step(params, opt, train_batch(cfg, batch, seq, seed))
    return sess


def capture_moe_dispatch(
    cfg,
    *,
    params=None,
    tokens=None,
    batch: int = 2,
    seq: int = 16,
    seed: int = 0,
    session: CaptureSession | None = None,
) -> CaptureSession:
    """Run the model's forward under capture: each expert layer records
    its MoE input rows and routing (``session.routes``, one
    :class:`MoeRoute` per layer) and their int8 wire image (stream ``x``).

    ``params`` default to ``init_params`` from the seed, ``tokens`` to
    (batch, seq) ids drawn uniformly from the seed."""
    import jax

    if cfg.moe is None or cfg.family != "moe":
        raise ValueError(
            f"config family {cfg.family!r} has no MoE block; "
            "capture_moe_dispatch needs a MoE config"
        )
    from repro.models import forward, init_params

    key = jax.random.key(seed)
    if params is None:
        params = init_params(cfg, key)
    if tokens is None:
        tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab)
    with capture(session) as sess:
        forward(params, cfg, tokens=tokens)
    return sess


def capture_lenet_conv(
    params=None,
    *,
    steps: int = 300,
    batch: int = 64,
    seed: int = 0,
    ckpt_dir: str | None = None,
    session: CaptureSession | None = None,
) -> CaptureSession:
    """Run a trained LeNet forward under capture: records the trained
    (honestly zero-clustered) conv kernels plus the input batch.  With
    ``params=None`` the model is trained in-repo first (restored from
    ``ckpt_dir`` when a checkpoint exists)."""
    import jax

    from repro.models import lenet

    if params is None:
        params, _ = lenet.train_lenet(
            steps=steps, batch=batch, seed=seed, ckpt_dir=ckpt_dir
        )
    images, _ = lenet.synth_batch(jax.random.key(seed), batch=8)
    with capture(session) as sess:
        lenet.lenet_forward(params, images)
    return sess


# --------------------------------------------------------------------------
# capture -> replay (artifact round-trip)
# --------------------------------------------------------------------------


def save_session(path: str, session: CaptureSession) -> None:
    """Persist a session's streams as one .npz (bytes + JSON manifest) —
    the capture->replay artifact (round-trip pinned in tests).  The
    device-resident ``routes`` are not persisted."""
    manifest = [
        {
            "scenario": s.scenario,
            "name": s.name,
            "kind": s.kind,
            "source_shape": list(s.source_shape),
            "meta": s.meta,
        }
        for s in session.streams
    ]
    arrays = {f"s{i}": s.data for i, s in enumerate(session.streams)}
    arrays["manifest"] = np.frombuffer(
        json.dumps({"name": session.name, "streams": manifest}).encode(),
        dtype=np.uint8,
    )
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def load_session(path: str) -> CaptureSession:
    """Rebuild a session from a :func:`save_session` artifact."""
    data = np.load(path)
    doc = json.loads(bytes(data["manifest"]).decode())
    sess = CaptureSession(doc.get("name", "capture"))
    for i, entry in enumerate(doc["streams"]):
        sess._add_bytes(
            entry["scenario"],
            entry["name"],
            np.asarray(data[f"s{i}"], dtype=np.uint8),
            tuple(entry["source_shape"]),
            entry["kind"],
            entry.get("meta", {}),
        )
    return sess
