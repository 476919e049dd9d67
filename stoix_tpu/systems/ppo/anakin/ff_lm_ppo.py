"""Anakin PPO with a token policy: a decoder (`network=olmoe`: OLMoE blocks
and a KV cache; `network=lfm2_moe`, `kanana2_moe`, `ling3_flash_moe`,
`laguna_xs2_moe`, `mellum2_moe`: networks/lfm2.py's stack of convolution, full
and window attention, latent-attention and delta-rule layers and a carry of
each kind of state) acts step by step
through its carry in the rollout and is updated teacher-forced over whole
sequences.

The first system in which the policy, not the env, is the work (the LM
post-training shape: generate a batch of fixed-length responses — from an
empty context or each from its prompt —, score them with a verifiable reward,
one pass of minibatch updates). Scaffolding — mesh, shard_map, GAE,
epoch/minibatch scans, `run_anakin_experiment` — is the canonical ff_ppo
template's; what differs:

  * ONE trunk behind its entry points over the same parameters: `step` (one
    decode step through the carry a rollout step, and the evaluator's greedy
    decode), `forward` (teacher-forced, in the loss) and, where the env has a
    prompt, `prefill` (the teacher-forced pass over the prefix that writes
    the carry). The network declares its carry: `init_carry(batch, max_len)`,
    `reset_carry(carry, done)`; this file names no network class.
    `ActorCriticParams.critic_params` holds only the scalar value head on the
    trunk's final hidden state; one loss, one backward pass.
  * The transition stores token ids, log-prob, value, reward, done — not
    logits (200 KB a token at 50,304 actions) and not the cache.
  * Minibatches are WHOLE sequences: envs are the sample axis of
    `ops/minibatch.shuffled_minibatch_epoch` (time is the feature axis).
  * The router's load-balancing loss (HF `router_aux_loss_coef`) joins the
    actor loss; TRAIN metrics carry the router's balance and the routed
    (token, slot) pairs a token, which equal top-k while nothing is dropped;
    of a network that holds one expert-parallel rank's share (`held`) also
    the pairs that landed on it, and what a selection bias re-routed.
  * The rollout's record rides out with the episode metrics
    (`rollout_action`, `rollout_log_prob`, `rollout_value`, [T, E] like
    them; with a prompt also `sequence_prompt` [P, E], once a sequence:
    envs/types.py `ONCE_A_SEQUENCE`): what was generated, from what, and
    what the policy said of it, for whoever audits a window from outside
    (the benchmark's reference does).

A PROMPT (`env.prompt_length` P > 0, envs/token_task.py): every episode has P
prefix tokens before its task token. The rollout is then `init_cache` ->
`prefill` (one teacher-forced pass over the prefixes, no head and no value,
under its own scope `prefill`, which lies BESIDE `rollout` and not under it:
`rollout` holds decode steps alone, with a prompt or without) -> the scan of
`rollout_length` decode steps,
whose inputs sit at positions P .. P + G - 1; the record gains the prefix once
a sequence, not a step; a minibatch's teacher-forced sequence is [prefix ;
response tokens] (P + G) with the head on the G response positions, and
PPO's clip, the value loss, the entropy and GAE are the G response steps' as
without one; the router's pairs of the prefill are counted like the rollout's
(TRAIN `prefill_routed_pairs_per_token`, `prefill_held_pairs_per_token`). The
evaluator's episodes start from their prompts prefilled alike
(`act_fn.start_carry`, evaluator.py). With P = 0 nothing of this is traced:
those learners are the programs they were.

The contract, checked at set-up: the env's episode length equals
`system.rollout_length`, so every rollout starts at a reset — with an empty
carry or with the one its prefill wrote — and the teacher-forced pass needs
nothing but the rollout's own record; every prompt is as long as the next;
every sequence of a batch is then at the same position at every step, in the
rollout and in the evaluator's decode alike, which is what licenses the carry
of ONE position that `network_functions` asks the network for; the carry
lives for the rollout only and is not part of the learner state (2 GB at the
OLMoE cell's widths, dead through the update). `arch.update_batch_size` must
be 1: a sort of all tokens by expert does not vmap, so there is no in-shard
replica axis and the state carries no [U] dimension. Prompts of unequal
length, packed sequences and episodes that span rollouts are not supported.

Layout (S = data shards, E = envs a shard):
  params / opt_states:    [...]          P()        (replicated)
  key:                    [S, 2]         P("data")
  env_state / timestep:   [S*E, ...]     P("data")
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, ExperimentOutput
from stoix_tpu.envs import wrappers
from stoix_tpu.envs.types import ONCE_A_SEQUENCE
from stoix_tpu.evaluator import carry_evaluator_setup
from stoix_tpu.networks import olmoe
from stoix_tpu.observability import SCOPES, annotate, get_logger, get_registry, span
from stoix_tpu.ops import (
    losses,
    shuffled_minibatch_epoch,
    truncated_generalized_advantage_estimation,
)
from stoix_tpu.ops.distributions import Categorical
from stoix_tpu.parallel import is_coordinator
from stoix_tpu.systems import anakin
from stoix_tpu.systems.runner import AnakinSetup, run_anakin_experiment
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.jax_utils import count_parameters
from stoix_tpu.utils.training import make_learning_rate

class LMPPOLearnerState(NamedTuple):
    params: ActorCriticParams
    opt_states: ActorCriticOptStates
    key: jax.Array
    env_state: Any
    timestep: Any


class LMTransition(NamedTuple):
    token: jax.Array  # the policy's input at this step
    action: jax.Array
    log_prob: jax.Array
    value: jax.Array
    reward: jax.Array
    done: jax.Array
    info: Dict[str, Any]


class LMNetworks(NamedTuple):
    """The two entry points and the value head, as pure functions."""

    # (actor_params, tokens [B, T]) -> (logits, hidden, stats); with a third argument n,
    # logits and hidden of the LAST n positions alone
    forward: Callable
    step: Callable  # (actor_params, cache, token [B]) -> (logits, hidden, cache, stats)
    value: Callable  # (critic_params, hidden) -> value
    init_cache: Callable  # (batch) -> the network's decode carry, empty
    reset_cache: Callable  # (carry, done [B]) -> carry: a new sequence where done
    # (actor_params, an empty carry, prefix [B, P]) -> (the carry at length P, stats): asked
    # for only where the env has a prompt
    prefill: Callable
    routed_layers: int  # layers with a router: the stats' leading axis
    # (offset, count) of the experts held here, of a network that holds one
    # expert-parallel rank's share; None: every expert is here.
    held: Optional[Tuple[int, int]] = None


def held_counts(expert_count: jax.Array, held: Tuple[int, int]) -> jax.Array:
    """expert_count [layers, experts] -> [held] routed pairs of the held
    experts, summed over layers."""
    return jnp.sum(expert_count, axis=0)[held[0]:held[0] + held[1]]


def lm_ppo_loss(
    networks: LMNetworks, params: ActorCriticParams, batch: Dict[str, jax.Array], *,
    clip_eps: float, ent_coef: float, vf_coef: float, aux_coef: float,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The loss on one minibatch of whole sequences (leaves [sequences, T]:
    token, action, log_prob, value, advantage, target): PPO clip, clipped
    value loss, entropy of the full categorical, the router's load-balancing
    loss. stoix_tpu/reference/olmoe.py::ppo_loss is its plain twin. With a
    `prefix` [sequences, P] the teacher-forced sequence is [prefix ; token]
    and the head runs on its last T positions: clip, value loss and entropy
    are the response's alone, the router's statistics every position's."""
    tokens, head_positions = batch["token"], ()
    if "prefix" in batch:
        tokens = jnp.concatenate([batch["prefix"], tokens], axis=1)
        head_positions = (batch["token"].shape[1],)
    logits, hidden, stats = networks.forward(params.actor_params, tokens, *head_positions)
    value = networks.value(params.critic_params, hidden)
    with annotate(SCOPES["lm_head"]):
        policy = Categorical(logits)
        log_prob = policy.log_prob(batch["action"])
        entropy = policy.entropy().mean()
    loss_actor = losses.ppo_clip_loss(log_prob, batch["log_prob"], batch["advantage"], clip_eps)
    value_loss = losses.clipped_value_loss(value, batch["value"], batch["target"], clip_eps)
    layers, num_tokens = stats["expert_count"].shape[0], tokens.size
    aux_loss = olmoe.load_balancing_loss(stats, num_tokens)
    total = loss_actor - ent_coef * entropy + vf_coef * value_loss + aux_coef * aux_loss
    load = jnp.sum(stats["expert_count"], axis=0).astype(jnp.float32)  # [experts], over layers
    info = {
        "total_loss": total, "actor_loss": loss_actor, "value_loss": value_loss,
        "entropy": entropy, "aux_loss": aux_loss,
        "expert_load_max_over_mean": jnp.max(load) / jnp.mean(load),
        "router_entropy": jnp.sum(stats["router_entropy_sum"]) / (layers * num_tokens),
        "routed_pairs_per_token": jnp.sum(load) / (layers * num_tokens),
    }
    if networks.held is not None:
        # One rank's share: the load that matters is the held experts'.
        top_k = stats["expert_index"].shape[-1]
        held = held_counts(stats["expert_count"], networks.held).astype(jnp.float32)
        info["expert_load_max_over_mean"] = jnp.max(held) / jnp.mean(held)
        info["held_pairs_per_token"] = jnp.sum(held) / (layers * num_tokens)
        info["dropped_pairs"] = layers * num_tokens * top_k - jnp.sum(load)
    if "bias_changed_sum" in stats:
        info["router_bias_changed_share"] = jnp.sum(stats["bias_changed_sum"]) / (
            layers * num_tokens
        )
    if "group_changed_sum" in stats:
        info["group_limited_changed_share"] = jnp.sum(stats["group_changed_sum"]) / (
            layers * num_tokens
        )
    return total, info


def get_learner_fn(
    env: envs.Environment, networks: LMNetworks, update_fns: Tuple[Callable, Callable], config: Any,
) -> Callable[[LMPPOLearnerState], ExperimentOutput]:
    """The PER-SHARD learner function (wrapped in shard_map by the set-up)."""
    actor_update, critic_update = update_fns
    gamma = float(config.system.gamma)
    gae_lambda = float(config.system.gae_lambda)
    clip_eps = float(config.system.clip_eps)
    ent_coef = float(config.system.ent_coef)
    vf_coef = float(config.system.vf_coef)
    aux_coef = float(config.system.router_aux_loss_coef)
    rollout_length = int(config.system.rollout_length)
    prompt_length = int(getattr(env, "prompt_length", 0))
    num_layers = networks.routed_layers

    def _pairs(stats: Dict[str, jax.Array]) -> Any:
        """What a rollout step keeps of the router's counts: the routed
        pairs, and of a held share also those that landed on it."""
        routed = jnp.sum(stats["expert_count"])
        if networks.held is None:
            return routed
        return routed, jnp.sum(held_counts(stats["expert_count"], networks.held))

    def _rollout(params: ActorCriticParams, key: jax.Array, env_state: Any, timestep: Any):
        def _env_step(carry: Tuple, _: Any):
            key, env_state, last_timestep, cache = carry
            key, policy_key = jax.random.split(key)
            with annotate(SCOPES["rollout_policy"]):
                token = last_timestep.observation.agent_view[..., 0]
                logits, hidden, cache, stats = networks.step(params.actor_params, cache, token)
                value = networks.value(params.critic_params, hidden)
                with annotate(SCOPES["lm_head"]):
                    policy = Categorical(logits)
                    action = policy.sample(seed=policy_key)
                    log_prob = policy.log_prob(action)
            with annotate(SCOPES["rollout_env"]):
                env_state, timestep = env.step(env_state, action)
            cache = networks.reset_cache(cache, timestep.last())
            transition = LMTransition(
                token=token, action=action, log_prob=log_prob, value=value,
                reward=timestep.reward, done=timestep.discount == 0.0,
                info=timestep.extras["episode_metrics"],
            )
            return (key, env_state, timestep, cache), (transition, _pairs(stats))

        prompt = None
        if prompt_length:
            # The episode's prefix, written into every layer's state by one
            # teacher-forced pass BEFORE the rollout's scope, not under it (what
            # reads `rollout` reads decode steps alone, as without a prompt): the
            # scan starts at position `prompt_length`.
            prefix = env.prompt(wrappers.unwrapped_state(env_state))
            with annotate(SCOPES["prefill"]):
                cache, stats = networks.prefill(
                    params.actor_params, networks.init_cache(timestep.reward.shape[0]), prefix
                )
            prompt = (prefix, _pairs(stats))
        # The scope is on the scan, not on its body: the loop op itself then
        # carries it, and with it the grouped-matmul kernels inside, which
        # XLA:TPU emits without a framework path of their own.
        with annotate(SCOPES["rollout"]):
            if not prompt_length:
                cache = networks.init_cache(timestep.reward.shape[0])
            (key, env_state, timestep, _), (traj, routed) = jax.lax.scan(
                _env_step, (key, env_state, timestep, cache), None, rollout_length
            )
        return key, env_state, timestep, traj, jax.tree.map(jnp.sum, routed), prompt

    @annotate(SCOPES["update_minibatch"])
    def _update_minibatch(train_state: Tuple, batch: Dict[str, jax.Array]):
        params, opt_states = train_state
        grads, info = jax.grad(lm_ppo_loss, argnums=1, has_aux=True)(
            networks, params, batch, clip_eps=clip_eps, ent_coef=ent_coef, vf_coef=vf_coef,
            aux_coef=aux_coef,
        )
        grads = jax.lax.pmean(grads, axis_name="data")
        actor_updates, actor_opt = actor_update(grads.actor_params, opt_states.actor_opt_state)
        critic_updates, critic_opt = critic_update(grads.critic_params, opt_states.critic_opt_state)
        params = ActorCriticParams(
            optax.apply_updates(params.actor_params, actor_updates),
            optax.apply_updates(params.critic_params, critic_updates),
        )
        return (params, ActorCriticOptStates(actor_opt, critic_opt)), info

    def _update_step(learner_state: LMPPOLearnerState, _: Any):
        params, opt_states = learner_state.params, learner_state.opt_states
        key, env_state, timestep, traj, routed, prompt = _rollout(
            params, learner_state.key, learner_state.env_state, learner_state.timestep
        )

        with annotate(SCOPES["gae"]):
            # Every rollout is one whole episode: the last step terminates
            # (discount 0), so no value beyond the trajectory is needed.
            v_t = jnp.concatenate([traj.value[1:], jnp.zeros_like(traj.value[:1])], axis=0)
            advantages, targets = truncated_generalized_advantage_estimation(
                traj.reward,
                gamma * (1.0 - traj.done.astype(jnp.float32)),
                gae_lambda,
                v_tm1=traj.value,
                v_t=v_t,
                truncation_t=jnp.zeros_like(traj.reward),
                standardize_advantages=bool(config.system.get("standardize_advantages", True)),
                impl=str(config.system.get("multistep_impl", "scan")),
            )

        # [T, E] -> [E, 1, T]: a sample of the shuffle is one whole sequence, and
        # a minibatch arrives as [sequences, T].
        data = {
            "token": traj.token, "action": traj.action, "log_prob": traj.log_prob,
            "value": traj.value, "advantage": advantages, "target": targets,
        }
        data = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1)[:, None], data)
        if prompt is not None:
            data["prefix"] = prompt[0][:, None]  # once a sequence: [E, 1, P]
        minibatch_epoch = shuffled_minibatch_epoch(
            _update_minibatch, (params, opt_states), data, config.system.num_minibatches
        )

        @annotate(SCOPES["update_epoch"])
        def _update_epoch(update_state: Tuple, _: Any):
            train_state, key = update_state
            key, shuffle_key = jax.random.split(key)
            train_state, info = minibatch_epoch(train_state, shuffle_key)
            return (train_state, key), info

        ((params, opt_states), key), info = jax.lax.scan(
            _update_epoch, ((params, opt_states), key), None, int(config.system.epochs)
        )
        learner_state = LMPPOLearnerState(params, opt_states, key, env_state, timestep)
        if networks.held is not None:
            routed, held = routed
            info["rollout_held_pairs_per_token"] = held.astype(jnp.float32) / (
                num_layers * traj.action.size
            )
        info["rollout_routed_pairs_per_token"] = routed.astype(jnp.float32) / (
            num_layers * traj.action.size
        )
        record = {
            "rollout_action": traj.action, "rollout_log_prob": traj.log_prob,
            "rollout_value": traj.value,
        }
        if prompt is not None:
            prefix, prefilled = prompt
            # [P, E], sequences last like the rest
            record[ONCE_A_SEQUENCE + "prompt"] = prefix.T
            if networks.held is not None:
                prefilled, held = prefilled
                info["prefill_held_pairs_per_token"] = held.astype(jnp.float32) / (
                    num_layers * prefix.size
                )
            info["prefill_routed_pairs_per_token"] = prefilled.astype(jnp.float32) / (
                num_layers * prefix.size
            )
        return learner_state, ({**traj.info, **record}, info)

    def learner_fn(learner_state: LMPPOLearnerState) -> ExperimentOutput:
        state = learner_state._replace(key=learner_state.key[0])  # [S=1 slice, 2] -> [2]
        state, (episode_info, info) = jax.lax.scan(
            _update_step, state, None, int(config.arch.num_updates_per_eval)
        )
        state = state._replace(key=state.key[None])
        info = jax.lax.pmean(info, axis_name="data")
        return ExperimentOutput(
            learner_state=state, episode_metrics=episode_info, train_metrics=info
        )

    return learner_fn


def build_networks(env: envs.Environment, config: Any) -> Tuple[Any, Any]:
    """(trunk + head, value head) from the network config, whatever network
    it names; the vocabulary is the env's action count. The seam a harness
    wraps to see the networks."""
    net_cfg = config.network
    actor = config_lib.instantiate(net_cfg.actor_network, vocab_size=env.num_actions)
    critic = config_lib.instantiate(net_cfg.critic_network)
    return actor, critic


def network_functions(actor: Any, critic: Any, max_len: int) -> LMNetworks:
    """The network's entry points and what it declares of itself: its carry
    (`init_carry`, `reset_carry`) of `max_len` positions (prompt and
    response), its routed layers, the share it holds. The carry is the one of
    sequences that move together: `learner_setup` checks that every episode
    is exactly one rollout, and every prompt is as long as the next."""
    return LMNetworks(
        forward=lambda params, tokens, *head_positions: actor.apply(
            params, tokens, *head_positions, method="forward"
        ),
        step=lambda params, cache, token: actor.apply(params, cache, token, method="step"),
        value=critic.apply,
        init_cache=lambda batch: actor.init_carry(batch, max_len, together=True),
        reset_cache=actor.reset_carry,
        prefill=lambda params, cache, tokens: actor.apply(params, cache, tokens, method="prefill"),
        routed_layers=int(actor.routed_layers),
        held=actor.held,
    )


def _carry_gauge() -> Any:
    return get_registry().gauge(
        "stoix_tpu_lm_carry_bytes",
        "bytes of the token policy's decode carry on one shard as the learner was set up, by "
        "kind of state: kv (keys and values, a row a position), window_kv (a window layer's ring "
        "of sliding_window rows), conv_tail (a short convolution's last inputs), latent (latent "
        "attention's compressed rows) or delta_state (a delta-rule layer's matrix a head and its "
        "convolutions' last inputs)",
    )


def _prompt_gauge() -> Any:
    return get_registry().gauge(
        "stoix_tpu_lm_prompt_tokens",
        "prefix tokens a sequence that the token policy prefills before it decodes, as the "
        "learner was set up (the env's prompt_length; 0: every rollout starts from an empty "
        "carry)",
    )


def _cache_write_gauge() -> Any:
    return get_registry().gauge(
        "stoix_tpu_lm_cache_write",
        "1 on the form in which the decode step writes a key/value row as the learner was set "
        "up, 0 on the other: slice (one position for all sequences, one slab in place) or "
        "scatter (a position a sequence); read from the shape of the carry's length",
    )


def _held_swiglu_gauge() -> Any:
    return get_registry().gauge(
        "stoix_tpu_held_swiglu_form",
        "1 on the form the held experts' SwiGLU takes in a rollout step as the learner was set "
        "up, 0 on the other: kernel (one weight-streaming Pallas pass over the chunk's rows, "
        "ops/held_swiglu.py) or ragged_dot (three grouped matmuls as XLA compiles them); chosen "
        "from the backend and the chunk's shape (networks/olmoe.py::held_swiglu_form); no "
        "series where every expert is held (moe's other path)",
    )


def set_held_swiglu_gauge(actor: Any, tokens: int) -> Optional[str]:
    """Record, and return, the form `actor`'s held experts take in a pass
    over `tokens` tokens (None: it holds every expert)."""
    gauge = _held_swiglu_gauge()
    for labels, _ in gauge.labels_and_values():  # an earlier learner's
        gauge.remove(dict(labels))
    if actor.held is None:
        return None
    taken = actor.held_swiglu_form(tokens)
    for form in ("kernel", "ragged_dot"):
        gauge.set(float(taken == form), {"form": form})
    return taken


def make_init_state(
    env: envs.Environment, config: Any, actor: Any, critic: Any,
    optims: Tuple[Any, Any], n_shards: int,
) -> Callable[[jax.Array], LMPPOLearnerState]:
    """key -> the whole initial learner state, as one traceable function."""
    actor_optim, critic_optim = optims

    def init_state(key: jax.Array) -> LMPPOLearnerState:
        key, actor_key, critic_key, env_key = jax.random.split(key, 4)
        tokens = jnp.zeros((1, 2), jnp.int32)
        actor_params = actor.init(actor_key, tokens, method="forward")
        hidden = jnp.zeros((1, 2, actor.hidden_size), jnp.float32)
        critic_params = critic.init(critic_key, hidden)
        env_state, timestep = env.reset(jax.random.split(env_key, int(config.arch.total_num_envs)))
        return LMPPOLearnerState(
            params=ActorCriticParams(actor_params, critic_params),
            opt_states=ActorCriticOptStates(
                actor_optim.init(actor_params), critic_optim.init(critic_params)
            ),
            key=jax.random.split(key, n_shards),
            env_state=env_state,
            timestep=timestep,
        )

    return init_state


def learner_setup(env: envs.Environment, config: Any, mesh: Mesh, key: jax.Array) -> AnakinSetup:
    rollout_length = int(config.system.rollout_length)
    if int(env.length) != rollout_length:
        raise ValueError(
            f"ff_lm_ppo needs the env's episode length ({int(env.length)}) to equal "
            f"system.rollout_length ({rollout_length}): every rollout is one whole sequence "
            "from an empty cache or from its prefilled prompt (episodes that span rollouts "
            "are not supported yet)"
        )
    prompt_length = int(getattr(env, "prompt_length", 0))
    if int(config.arch.get("update_batch_size", 1)) != 1:
        raise ValueError("ff_lm_ppo has no in-shard replica axis: arch.update_batch_size must be 1")
    n_shards = int(mesh.shape["data"])
    envs_per_shard = int(config.arch.total_num_envs) // n_shards
    if envs_per_shard % int(config.system.num_minibatches) != 0:
        raise ValueError(
            f"{envs_per_shard} sequences a shard do not divide into "
            f"system.num_minibatches={int(config.system.num_minibatches)} minibatches of whole "
            "sequences"
        )
    config.system.action_dim = env.num_actions

    actor, critic = build_networks(env, config)
    networks = network_functions(actor, critic, prompt_length + rollout_length)
    epochs, minibatches = int(config.system.epochs), int(config.system.num_minibatches)
    make_optim = lambda lr: optax.chain(
        optax.clip_by_global_norm(float(config.system.max_grad_norm)),
        optax.adam(make_learning_rate(float(lr), config, epochs, minibatches), eps=1e-5),
    )
    actor_optim = make_optim(config.system.actor_lr)
    critic_optim = make_optim(config.system.critic_lr)

    state_specs = LMPPOLearnerState(
        params=P(), opt_states=P(), key=P("data"), env_state=P("data"), timestep=P("data")
    )

    init_state = make_init_state(env, config, actor, critic, (actor_optim, critic_optim), n_shards)

    # The state is built ON its shardings by one jitted program: 0.63 G
    # parameters and two Adam moments are never held twice.
    with span("network_init"):
        out_shardings = LMPPOLearnerState(*(
            jax.tree.map(lambda _: NamedSharding(mesh, spec), field)
            for field, spec in zip(jax.eval_shape(init_state, key), state_specs)
        ))
        learner_state = jax.jit(init_state, out_shardings=out_shardings)(key)

    learn_per_shard = get_learner_fn(
        env, networks, (actor_optim.update, critic_optim.update), config
    )
    # XLA options a network's yaml names for its learner on a TPU
    # (configs/network/ling3_flash_moe.yaml has one, and why); none elsewhere.
    on_tpu = jax.default_backend() == "tpu"
    options = config.network.get("learner_compiler_options") if on_tpu else None
    learn = anakin.shardmap_learner(
        learn_per_shard, mesh, state_specs, episode_metrics_spec=P(None, None, "data"),
        compiler_options=options,
    )

    for labels, _ in _carry_gauge().labels_and_values():  # an earlier learner's kinds
        _carry_gauge().remove(dict(labels))
    for kind, size in actor.carry_bytes(envs_per_shard, prompt_length + rollout_length).items():
        _carry_gauge().set(size, {"kind": kind})
    _prompt_gauge().set(prompt_length)
    together = jax.eval_shape(lambda: networks.init_cache(envs_per_shard)).length.ndim == 0
    for form, took in (("slice", together), ("scatter", not together)):
        _cache_write_gauge().set(float(took), {"form": form})
    held_form = set_held_swiglu_gauge(actor, envs_per_shard)

    if is_coordinator():
        get_logger("stoix_tpu.setup").info(
            "[setup] %s parameters | mesh %s | %s sequences x %s tokens an update%s%s",
            f"{count_parameters(learner_state.params):,}", dict(mesh.shape),
            config.arch.total_num_envs, rollout_length,
            f" after a prompt of {prompt_length}" if prompt_length else "",
            f" | the held experts' SwiGLU of a rollout step: {held_form}" if held_form else "",
        )

    greedy = bool(config.arch.get("evaluation_greedy", False))

    def act_fn(params: Any, cache: Any, observation: Any, done: jax.Array, keys: jax.Array):
        """The evaluator's batched step through the same `step`."""
        cache = networks.reset_cache(cache, done)
        logits, _, cache, _ = networks.step(params, cache, observation.agent_view[..., 0])
        with annotate(SCOPES["lm_head"]):
            action = (
                jnp.argmax(logits, axis=-1) if greedy
                else jax.random.categorical(keys[0], logits, axis=-1)
            )
        return cache, action

    act_fn.init_carry = networks.init_cache  # the evaluator's carry is the network's own
    if prompt_length:

        def start_carry(params: Any, env_state: Any) -> Any:
            """The evaluator's carry at the first step of its episodes: their
            prompts prefilled, as the rollout's."""
            prefix = env.prompt(wrappers.unwrapped_state(env_state))
            with annotate(SCOPES["prefill"]):
                return networks.prefill(params, networks.init_cache(prefix.shape[0]), prefix)[0]

        act_fn.start_carry = start_carry

    return AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=act_fn,
        # The live actor parameters themselves, not a copy (the runner reads
        # them before the next donating dispatch).
        eval_params_fn=lambda s: s.params.actor_params,
    )


def run_experiment(config: Any) -> float:
    """Train; returns the final evaluation episode-return mean."""
    return run_anakin_experiment(
        config, learner_setup, evaluator_setup_fn=carry_evaluator_setup()
    )


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_lm_ppo.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
