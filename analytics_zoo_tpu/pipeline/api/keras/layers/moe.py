"""Mixture-of-Experts FFN with expert parallelism.

Absent from the reference (like ring/Ulysses sequence parallelism —
SURVEY.md §2.10 lists EP as "NO"); first-class here because expert
parallelism is one of the shardings a TPU-native framework must scale
(round goals: dp/tp/sp/ep). Design is the XLA-friendly Switch
Transformer formulation:

- router: tokens → softmax over n_experts, top-1 gate;
- capacity: each expert takes at most ``capacity_factor · T/E`` tokens
  (overflow dropped — keeps every shape static for the compiler);
- dispatch/combine are one-hot einsums, NOT gathers — under a mesh
  with an ``expert`` axis and expert-stacked params sharded on it,
  GSPMD lowers them to all-to-alls over ICI;
- expert FFNs are ONE stacked einsum (E, d, h): no per-expert Python
  loop, one MXU-dense contraction.

Aux load-balancing loss (Switch eq. 4) is exposed via
``regularization_loss`` so the Estimator adds it automatically.

:class:`GroupLimitedMoE` is the serving-side expert layer of the
DeepSeek-V2 family, a feed-forward *part* of
`layers.decoder.PatternDecoder`: top-k of all experts by
group-limited greedy routing, shared experts always on, no capacity
and no dropped token, and a layer that is TOLD WHICH EXPERTS IT
HOLDS: it routes over all of them and computes the part of the
result its own experts give (what expert parallelism asks of a
layer; the exchange is the caller's).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import activations, initializers
from analytics_zoo_tpu.ops.grouped_matmul import grouped_matmul
from analytics_zoo_tpu.pipeline.api.keras.engine import KerasLayer, Shape
from analytics_zoo_tpu.pipeline.api.keras.layers.transformer import \
    _normal


def gated_mlp(x, w_gate, w_up, w_down):
    """SwiGLU: ``(silu(x W_g) * (x W_u)) W_d``."""
    dt = x.dtype
    return (jax.nn.silu(x @ w_gate.astype(dt)) *
            (x @ w_up.astype(dt))) @ w_down.astype(dt)


def relu2_mlp(x, w_up, w_down):
    """The non-gated squared ReLU: ``relu(x W_u)^2 W_d``."""
    dt = x.dtype
    return jnp.square(jax.nn.relu(x @ w_up.astype(dt))) @ \
        w_down.astype(dt)


# the experts' activations: "swiglu" (gate, up and down leaves) and
# "relu2" (up and down)
ACTIVATIONS = ("swiglu", "relu2")


class GatedMLP:
    """Dense SwiGLU feed-forward part of width ``width``."""

    step_counters = ()

    def __init__(self, hidden_size: int, width: int):
        self.hidden_size, self.width = int(hidden_size), int(width)

    def build(self, rng, stddev: float) -> dict:
        h, m = self.hidden_size, self.width
        k = jax.random.split(rng, 3)
        return {"gate": _normal(k[0], (h, m), stddev),
                "up": _normal(k[1], (h, m), stddev),
                "down": _normal(k[2], (m, h), stddev)}

    def __call__(self, p, x, valid=None, scope="decode"):
        """``x``: (N, hidden) tokens. Returns ``(y, None)``."""
        del valid
        with jax.named_scope(f"zoo:{scope}/mlp"):
            return gated_mlp(x, p["gate"], p["up"], p["down"]), None


class GroupLimitedMoE:
    """Expert feed-forward part: ``n_experts`` routed SwiGLU experts
    of width ``width`` in ``n_group`` groups, ``top_k`` a token
    chosen among the ``topk_group`` best groups (a group's score is
    its best expert's), weights the scores themselves times
    ``routed_scaling``; ``n_shared`` shared experts (one SwiGLU of
    ``n_shared * width``) always on, added to the routed mix or
    averaged with it (``shared_combine``).

    ``scoring``: ``"softmax"`` over the router's outputs
    (DeepSeek-V2), or ``"sigmoid"`` of each (DeepSeek-V3's
    ``noaux_tc``): a ``router_bias`` a expert is then added to the
    scores for CHOOSING and takes no part in the weights (with
    ``selection_bias=False`` there is no such leaf, and the scores
    choose alone: Command A's router).
    ``norm_topk``: the chosen experts' weights divided by their sum.
    ``shared_combine``: ``"sum"``, ``r + s`` of the routed mix ``r``
    and the shared path ``s``, or ``"average"``, ``(r + s) / 2``
    (Command A's ``shared_expert_combination_strategy``).
    ``n_group=1, scoring="sigmoid", norm_topk=True`` is the ungrouped
    router (every expert is a candidate for every token): the class
    keeps its name, the group limit is then no limit.

    ``experts_held = (first, count)``: the experts whose weights
    this layer holds. It routes over all ``n_experts`` and adds, for
    each token, only what its chosen experts in ``[first, first +
    count)`` give; what the others would add is left out. The routed
    part is a grouped matrix product over the held experts
    (`ops/grouped_matmul.py`'s kernel on the assignments sorted by
    expert): an expert no token chose is not read, and tokens marked
    not ``valid`` (padding, idle slots) choose none.
    """

    # per call, as int32: (token, expert) assignments of valid
    # tokens, those that fell on held experts, the busiest held
    # expert's, and the held experts they reached
    step_counters = ("zoo_tpu_moe_assignments_total",
                     "zoo_tpu_moe_assignments_held_total",
                     "zoo_tpu_moe_expert_load_max_total",
                     "zoo_tpu_moe_experts_reached_total")

    @staticmethod
    def record(counts):
        """Add a decode step's four counts (summed over the expert
        layers) to the counters of :attr:`step_counters`."""
        from analytics_zoo_tpu.common import observability as obs
        total, held, busiest, reached = (int(c) for c in counts)
        obs.counter(
            "zoo_tpu_moe_assignments_total",
            help="(token, expert) assignments of active slots in "
            "decode steps, over the expert layers").inc(total)
        obs.counter(
            "zoo_tpu_moe_assignments_held_total",
            help="assignments that fell on experts held "
            "here").inc(held)
        obs.counter(
            "zoo_tpu_moe_expert_load_max_total",
            help="the busiest held expert's assignments, a layer "
            "and step").inc(busiest)
        obs.counter(
            "zoo_tpu_moe_experts_reached_total",
            help="held experts that a valid row reached, a layer and "
            "token block: the expert weight sets the grouped product "
            "streams").inc(reached)

    def __init__(self, hidden_size: int, width: int, n_experts: int,
                 top_k: int, n_group: int = 1, topk_group: int = 1,
                 n_shared: int = 0, routed_scaling: float = 1.0,
                 experts_held: "Optional[tuple]" = None,
                 token_block: int = 2048, scoring: str = "softmax",
                 norm_topk: bool = False, activation: str = "swiglu",
                 shared_width: Optional[int] = None,
                 selection_bias: bool = True,
                 shared_combine: str = "sum"):
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r} not 'softmax' or "
                             "'sigmoid'")
        if shared_combine not in ("sum", "average") or (
                shared_combine == "average" and not n_shared):
            raise ValueError(f"shared_combine {shared_combine!r}: "
                             "'sum', or 'average' of a shared path")
        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r} not one of "
                             f"{ACTIVATIONS}")
        self.scoring, self.norm_topk = scoring, bool(norm_topk)
        self.selection_bias = scoring == "sigmoid" and bool(
            selection_bias)
        self.shared_combine = shared_combine
        self.activation = activation
        self.hidden_size, self.width = int(hidden_size), int(width)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.n_shared = int(n_shared)
        self.shared_width = int(shared_width) if shared_width \
            else self.n_shared * int(width)
        self.routed_scaling = float(routed_scaling)
        first, count = experts_held or (0, self.n_experts)
        self.first, self.n_held = int(first), int(count)
        self.token_block = int(token_block)
        if self.n_experts % self.n_group:
            raise ValueError("n_experts must divide by n_group")
        if not 0 <= self.first <= self.first + self.n_held \
                <= self.n_experts:
            raise ValueError(f"experts_held {experts_held} outside "
                             f"[0, {self.n_experts})")
        if self.top_k > self.topk_group * (self.n_experts //
                                           self.n_group):
            raise ValueError("top_k exceeds the experts of the "
                             "groups kept")

    def build(self, rng, stddev: float) -> dict:
        h, m, e = self.hidden_size, self.width, self.n_held
        k = jax.random.split(rng, 7)
        n = lambda key, shape: _normal(key, shape, stddev)
        gated = self.activation == "swiglu"
        out = {"router": n(k[0], (h, self.n_experts))}
        if gated:
            out["experts_gate"] = n(k[1], (e, h, m))
        out.update(experts_up=n(k[2], (e, h, m)),
                   experts_down=n(k[3], (e, m, h)))
        if self.selection_bias:
            out["router_bias"] = jnp.zeros((self.n_experts,),
                                           jnp.float32)
        if self.n_shared:
            ms = self.shared_width
            if gated:
                out["shared_gate"] = n(k[4], (h, ms))
            out.update(shared_up=n(k[5], (h, ms)),
                       shared_down=n(k[6], (ms, h)))
        return out

    def route(self, p, x):
        """``(experts (N, top_k) int32, weights (N, top_k) f32)`` of
        tokens ``x`` (N, hidden), over all ``n_experts``. The router
        runs in float32 at the highest matmul precision: it is tiny,
        and a rounded score moves a token to another expert."""
        logits = jnp.dot(
            x.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "sigmoid":
            scores = choice = jax.nn.sigmoid(logits)
            if self.selection_bias:
                choice = scores + p["router_bias"].astype(jnp.float32)
        else:
            scores = choice = jax.nn.softmax(logits, axis=-1)
        n, g = scores.shape[0], self.n_group
        if g > 1:
            best = choice.reshape(n, g, -1).max(axis=-1)
            _, keep = jax.lax.top_k(best, self.topk_group)
            kept = jnp.zeros((n, g), jnp.bool_).at[
                jnp.arange(n)[:, None], keep].set(True)
            choice = jnp.where(jnp.repeat(
                kept, self.n_experts // g, axis=1), choice,
                0.0 if self.scoring == "softmax" else -jnp.inf)
        weights, experts = jax.lax.top_k(choice, self.top_k)
        if self.scoring == "sigmoid":
            weights = jnp.take_along_axis(scores, experts, axis=1)
        if self.norm_topk:
            weights = weights / (jnp.sum(weights, axis=-1,
                                         keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), \
            weights * self.routed_scaling

    def routed(self, p, x, experts, weights, valid):
        """The held experts' part for tokens ``x`` (N, hidden) and
        the four counts of :attr:`step_counters`."""
        n, k = experts.shape
        local = experts - self.first
        held = jnp.logical_and(local >= 0, local < self.n_held)
        held = jnp.logical_and(held, valid[:, None]).reshape(-1)
        # held assignments first, by expert; the rest behind them,
        # where no group reaches
        key = jnp.where(held, local.reshape(-1), self.n_held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((self.n_held + 1,), jnp.int32).at[key].add(
            1)[:self.n_held]
        rows = jnp.take(x, order // k, axis=0)
        product = lambda a, name: grouped_matmul(a, p[name], sizes)
        if self.activation == "relu2":
            act = jnp.square(jax.nn.relu(product(rows, "experts_up")))
        else:
            act = jax.nn.silu(product(rows, "experts_gate")) * \
                product(rows, "experts_up")
        out = product(act, "experts_down")
        # back to (token, choice) order; a row past the last group is
        # whatever the kernel left there, and counts for nothing
        back = jnp.argsort(order)
        out = jnp.where(held[:, None], jnp.take(out, back, axis=0), 0)
        w = weights.reshape(-1, 1).astype(jnp.float32)
        y = (out.astype(jnp.float32) * w).reshape(n, k, -1).sum(axis=1)
        counts = jnp.stack([k * jnp.sum(valid, dtype=jnp.int32),
                            jnp.sum(held, dtype=jnp.int32),
                            jnp.max(sizes, initial=0),
                            jnp.sum(sizes > 0, dtype=jnp.int32)])
        return y.astype(x.dtype), counts

    def __call__(self, p, x, valid=None, scope="decode"):
        """``x``: (N, hidden) tokens; ``valid`` (N,) bool. Returns
        ``(y (N, hidden), counts (4,) int32)``. Past ``token_block``
        tokens the routed part runs block by block, so that its
        sorted copies of the tokens stay small; each block streams the
        experts it reaches once, and the reached count is their sum
        over the blocks."""
        n = x.shape[0]
        if valid is None:
            valid = jnp.ones((n,), jnp.bool_)

        def block(xb, vb):
            with jax.named_scope(f"zoo:{scope}/moe_route"):
                experts, weights = self.route(p, xb)
            with jax.named_scope(f"zoo:{scope}/moe_experts"):
                return self.routed(p, xb, experts, weights, vb)

        tb = self.token_block
        if n > tb and n % tb == 0:
            y, counts = jax.lax.map(
                lambda a: block(*a), (x.reshape(n // tb, tb, -1),
                                      valid.reshape(n // tb, tb)))
            y = y.reshape(n, -1)
            counts = jnp.stack([counts[:, 0].sum(), counts[:, 1].sum(),
                                counts[:, 2].max(), counts[:, 3].sum()])
        else:
            y, counts = block(x, valid)
        if self.n_shared:
            with jax.named_scope(f"zoo:{scope}/moe_shared"):
                s = relu2_mlp(x, p["shared_up"], p["shared_down"]) \
                    if self.activation == "relu2" else \
                    gated_mlp(x, p["shared_gate"], p["shared_up"],
                              p["shared_down"])
                y = y + s if self.shared_combine == "sum" else \
                    (0.5 * (y.astype(jnp.float32) + s.astype(
                        jnp.float32))).astype(y.dtype)
        return y, counts


class MoE(KerasLayer):
    """Switch-style top-1 MoE FFN over (B, T, d) inputs.

    Params carry a leading expert axis; pass ``expert_axis="expert"``
    (with that axis in the mesh) to shard experts across devices —
    dispatch/combine become all-to-alls (expert parallelism).
    """

    # consumed by shard_params_ep: these params have a stacked leading
    # expert dim (routers and other layers replicate under EP)
    expert_stacked_params = ("w_in", "b_in", "w_out", "b_out")

    def __init__(self, n_experts: int, hidden_dim: int,
                 capacity_factor: float = 1.25,
                 activation="gelu", aux_loss_weight: float = 0.01,
                 init="glorot_uniform",
                 expert_axis: Optional[str] = None,
                 input_shape=None, name=None, **kwargs):
        super().__init__(input_shape=input_shape, name=name, **kwargs)
        self.n_experts = int(n_experts)
        self.hidden_dim = int(hidden_dim)
        self.capacity_factor = float(capacity_factor)
        self.activation = activations.get(activation)
        self.aux_loss_weight = float(aux_loss_weight)
        self.kernel_init = initializers.get(init)
        self.expert_axis = expert_axis
        self._last_aux = None

    def build(self, rng, input_shape: Shape) -> dict:
        d = input_shape[-1]
        e, h = self.n_experts, self.hidden_dim
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "router_kernel": self.kernel_init(k1, (d, e)),
            "w_in": self.kernel_init(k2, (e, d, h)),
            "b_in": jnp.zeros((e, h), jnp.float32),
            "w_out": self.kernel_init(k3, (e, h, d)),
            "b_out": jnp.zeros((e, d), jnp.float32),
        }

    def _maybe_shard(self, x, spec_axes):
        """Annotate expert-stacked intermediates so GSPMD keeps the
        expert dim on the expert axis (all-to-all at the boundaries)."""
        if not self.expert_axis:
            return x
        from analytics_zoo_tpu.common.nncontext import get_nncontext
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = get_nncontext().mesh
        if self.expert_axis not in mesh.axis_names:
            return x
        spec = [self.expert_axis if a == "E" else None
                for a in spec_axes]
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    def call(self, params, x, *, training=False, rng=None):
        b, t, d = x.shape
        e = self.n_experts
        cap = max(int(self.capacity_factor * t / e), 1)

        logits = x @ params["router_kernel"].astype(x.dtype)  # (B,T,E)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate = jnp.max(probs, axis=-1)                        # (B,T)
        expert_idx = jnp.argmax(probs, axis=-1)               # (B,T)

        # position of each token within its expert's queue
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
        pos = jnp.cumsum(onehot, axis=1) * onehot              # (B,T,E)
        within_cap = (pos <= cap) & (onehot > 0)
        # dispatch tensor (B, T, E, C): token t → slot pos-1 of expert
        slot = jax.nn.one_hot(
            (pos - 1).astype(jnp.int32), cap, dtype=jnp.float32)
        dispatch = within_cap[..., None].astype(jnp.float32) * slot

        # (B,T,E,C) × (B,T,d) → (E, B, C, d): the all-to-all boundary.
        # Routing stats stay f32; the expert FFN — the layer's dominant
        # FLOPs — runs in the compute dtype (bf16 under the mixed
        # policy) so EP keeps the MXU 2x rate.
        cdt = x.dtype
        xe = jnp.einsum("btec,btd->ebcd", dispatch.astype(cdt), x)
        xe = self._maybe_shard(xe, "E***")
        h = jnp.einsum("ebcd,edh->ebch", xe,
                       params["w_in"].astype(cdt)) + \
            params["b_in"].astype(cdt)[:, None, None, :]
        h = self.activation(h) if self.activation else h
        ye = jnp.einsum("ebch,ehd->ebcd", h,
                        params["w_out"].astype(cdt)) + \
            params["b_out"].astype(cdt)[:, None, None, :]
        ye = self._maybe_shard(ye, "E***")

        combine = (dispatch * gate[..., None, None]).astype(cdt)
        y = jnp.einsum("btec,ebcd->btd", combine, ye)

        # Switch aux loss: E · Σ_e fraction_tokens_e · mean_prob_e
        frac = jnp.mean(onehot, axis=(0, 1))
        mean_p = jnp.mean(probs, axis=(0, 1))
        self._last_aux = e * jnp.sum(frac * mean_p)
        return y.astype(x.dtype)

    def regularization_loss(self, params) -> jnp.ndarray:
        # consume-once: the aux value is a tracer from the forward
        # trace; the Estimator reads it inside the SAME trace right
        # after apply(). An eager/out-of-trace read (leaked tracer)
        # falls back to 0 instead of crashing.
        aux, self._last_aux = self._last_aux, None
        if aux is None or self.aux_loss_weight == 0.0:
            return jnp.zeros((), jnp.float32)
        try:
            return self.aux_loss_weight * aux
        except Exception:
            from analytics_zoo_tpu.common.nncontext import logger
            logger.warning(
                "MoE aux loss dropped: regularization_loss was called "
                "outside the trace that ran forward (custom training "
                "loops must compute it in the same jit as apply)")
            return jnp.zeros((), jnp.float32)

    def compute_output_shape(self, input_shape: Shape) -> Shape:
        return input_shape
