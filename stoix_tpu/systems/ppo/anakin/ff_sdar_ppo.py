"""Anakin PPO with a token policy that generates by DIFFUSION OVER BLOCKS:
the SDAR mixture-of-experts decoder (networks/sdar.py, one expert-parallel
rank's share of each layer) fills a response block by block, several tokens
a model pass, and is updated teacher-forced on `[clean copy ; noisy copies]`.

Scaffolding — mesh, shard_map, GAE, the shuffle over whole sequences,
`run_anakin_experiment`, the carry evaluator — is ff_lm_ppo's. What differs
is what a step is:

  * Generation. A sequence is a prompt block of B task tokens and L / B
    response blocks, left to right. A response block starts as B mask tokens
    and is filled by S DENOISE PASSES: the block's B positions go through the
    model together (`SdarLM.block_step`: they attend the committed blocks in
    the cache and one another, nothing is written), every still-masked
    position i samples a_i ~ p_i (the mask id's logit at -inf; evaluation:
    argmax) with confidence c_i = p_i(a_i), and the COMMIT SET — the B / S
    masked positions of largest confidence, ties to the lower position —
    takes its tokens; the rest stay masked. After pass S the block is whole
    and one COMMIT PASS writes its keys and values into the cache. The
    schedule is static, so every sequence is at the same block and pass.
  * The PPO step is one denoise pass (the trace-level objective of TraceRL,
    Wang et al. 2025, "Revolutionizing reinforcement learning framework for
    diffusion large language models", the public RL recipe of the SDAR
    family: the policy is optimised along the trajectory of denoise steps it
    actually took). Stored log-prob: the sum over the commit set of log
    p_i(a_i); which positions commit is a deterministic function of the
    sampled tokens and has no term of its own. Value: the mean over the
    block's B positions of the value head on that pass's final hidden states.
    Reward: the block token task's, terminal. GAE runs over the S * L / B
    steps of the episode.
  * The update (`sdar_ppo_loss`) builds, from the stored record alone, the
    clean sequence and S noisy copies (copy s holds every block as it stood
    before its pass s) and runs `SdarLM.trunk_copies` under the block mask:
    step (b, s)'s new log-prob and value are read at copy s, block b. Every
    response position is committed in exactly one pass, so the head runs on
    L positions a sequence, not S * L. At unchanged parameters the
    recomputed log-probs and values equal the stored ones (ratio 1).
  * The transition holds the block before the pass, the commit set, the B
    sampled tokens, log-prob, value, reward, done; the rollout's record rides
    out with the episode metrics (`rollout_block`, `rollout_commit`,
    `rollout_token`, `rollout_log_prob`, `rollout_value`) for whoever audits
    a window from outside (the benchmark's reference does).
  * TRAIN metrics carry, beside ff_lm_ppo's: `decode_passes_per_token`
    (model passes of the rollout, denoise and commit, a token generated),
    `tokens_per_denoise_pass`, `commit_confidence_mean`, and the share's
    counters — `routed_pairs_per_token` (top-k, over all experts) and
    `held_pairs_per_token` (those that land on the experts held here, a
    layer; 1.0 under uniform routing), in the rollout and in the update;
    `expert_load_max_over_mean` is over the held experts' counts.

v1 contract, checked at set-up: one rollout is one whole episode
(`system.rollout_length` = the env's steps an episode), the prompt is one
block, `arch.update_batch_size` is 1. The cache lives for the rollout only.

Layout as ff_lm_ppo: params and opt_states replicated, key / env_state /
timestep sharded over "data".
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stoix_tpu import envs
from stoix_tpu.base_types import ActorCriticOptStates, ActorCriticParams, ExperimentOutput
from stoix_tpu.evaluator import carry_evaluator_setup
from stoix_tpu.networks import olmoe, sdar
from stoix_tpu.observability import SCOPES, annotate, get_logger, get_registry, span
from stoix_tpu.ops import (
    losses,
    shuffled_minibatch_epoch,
    truncated_generalized_advantage_estimation,
)
from stoix_tpu.ops.distributions import Categorical
from stoix_tpu.ops.qk_norm_rope import norm_rope_form
from stoix_tpu.parallel import is_coordinator
from stoix_tpu.systems import anakin
from stoix_tpu.systems.ppo.anakin.ff_lm_ppo import (
    LMPPOLearnerState, held_counts, set_held_swiglu_gauge,
)
from stoix_tpu.systems.runner import LAST_RUN_STATS, AnakinSetup, run_anakin_experiment
from stoix_tpu.utils import config as config_lib
from stoix_tpu.utils.jax_utils import count_parameters
from stoix_tpu.utils.training import make_learning_rate


class SdarTransition(NamedTuple):
    block: jax.Array  # [E, B] the block before the pass (mask id where masked)
    commit: jax.Array  # [E, B] bool: the positions this pass committed
    token: jax.Array  # [E, B] what every position sampled
    log_prob: jax.Array  # [E] sum over the commit set
    value: jax.Array
    reward: jax.Array
    done: jax.Array
    info: Dict[str, Any]


class SdarNetworks(NamedTuple):
    """The entry points and the heads, as pure functions."""

    # (actor_params, cache, tokens [E, B], block, write) -> (hidden, cache, stats)
    block_step: Callable
    trunk_copies: Callable  # (actor_params, clean [n, B + R], noisy [n, S, R]) -> (hidden, stats)
    head: Callable  # (actor_params, hidden) -> logits over the slice
    value: Callable  # (critic_params, hidden) -> value a position
    init_cache: Callable  # (batch) -> BlockCache
    mask_id: int
    passes: int  # S: denoise passes a block
    held: Tuple[int, int]  # (offset, count) of the experts held here


class Choice(NamedTuple):
    token: jax.Array  # [E, B] sampled (or greedy) at every position
    commit: jax.Array  # [E, B] bool
    block: jax.Array  # [E, B] the block after the pass
    log_prob: jax.Array  # [E] sum over the commit set
    confidence: jax.Array  # [E] mean over the commit set


def choose(
    logits: jax.Array, block: jax.Array, mask_id: int, count: int, key: Optional[jax.Array]
) -> Choice:
    """One denoise pass's decision from its logits [E, B, V]: sample (argmax
    without a key) at every position with the mask id excluded, commit the
    `count` still-masked positions of largest confidence."""
    allowed = jnp.arange(logits.shape[-1]) != mask_id
    policy = Categorical(logits, mask=allowed)
    token = policy.mode() if key is None else policy.sample(seed=key)
    log_prob = policy.log_prob(token)  # [E, B]
    masked = block == mask_id
    confidence = jnp.where(masked, jnp.exp(log_prob), -1.0)
    _, chosen = jax.lax.top_k(confidence, count)  # equal confidences: the lower position first
    commit = jnp.any(chosen[..., None] == jnp.arange(block.shape[-1]), axis=-2) & masked
    return Choice(
        token=token,
        commit=commit,
        block=jnp.where(commit, token, block).astype(block.dtype),
        log_prob=jnp.sum(jnp.where(commit, log_prob, 0.0), axis=-1),
        confidence=jnp.sum(jnp.where(commit, confidence, 0.0), axis=-1) / count,
    )


def block_value(networks: SdarNetworks, critic_params: Any, hidden: jax.Array) -> jax.Array:
    """hidden [..., B, D] -> the mean over the block of the value head."""
    return jnp.mean(networks.value(critic_params, hidden), axis=-1)


def record_copies(batch: Dict[str, jax.Array], passes: int) -> Dict[str, jax.Array]:
    """The update's inputs from the stored record (leaves [n, T, B] with T =
    blocks * passes steps, `prompt` [n, B]): the clean sequence [n, B + R],
    the noisy copies [n, S, R], and for every response position [n, blocks,
    B] the pass that committed it and the token it took."""
    n, steps, size = batch["block"].shape
    blocks = steps // passes
    by_pass = lambda x: x.reshape(n, blocks, passes, size)
    before, commit, token = (by_pass(batch[name]) for name in ("block", "commit", "token"))
    final = jnp.where(commit[:, :, -1], token[:, :, -1], before[:, :, -1])
    pass_of = jnp.argmax(commit, axis=2)  # [n, blocks, B]
    return {
        "clean": jnp.concatenate([batch["prompt"], final.reshape(n, blocks * size)], axis=1),
        "noisy": jnp.swapaxes(before, 1, 2).reshape(n, passes, blocks * size),
        "pass_of": pass_of,
        "committed": jnp.take_along_axis(token, pass_of[:, :, None], axis=2)[:, :, 0],
    }


def teacher_forced(
    networks: SdarNetworks, params: ActorCriticParams, batch: Dict[str, jax.Array]
) -> Dict[str, Any]:
    """What the update recomputes of a stored record (leaves [n, T(, B)]:
    block, commit, token; prompt [n, B]): every denoise step's log-prob and
    value [n, T], the mean entropy of the categoricals the committed tokens
    were drawn from, the router's stats over every position of `[clean ;
    noisy copies]` and their number."""
    passes = networks.passes
    n, steps, size = batch["block"].shape
    blocks = steps // passes
    copies = record_copies(batch, passes)
    hidden, stats = networks.trunk_copies(params.actor_params, copies["clean"], copies["noisy"])
    hidden = hidden.reshape(n, passes, blocks, size, -1)
    # [n, S, blocks] -> step order (block-major): [n, T]
    value = block_value(networks, params.critic_params, hidden)
    value = jnp.swapaxes(value, 1, 2).reshape(n, steps)
    # Every response position is committed in exactly one pass: the head runs
    # on that pass's hidden state of it, L positions a sequence, not S * L.
    pass_of = copies["pass_of"]
    picked = jnp.take_along_axis(hidden, pass_of[:, None, :, :, None], axis=1)[:, 0]
    logits = networks.head(params.actor_params, picked)
    with annotate(SCOPES["lm_head"]):
        policy = Categorical(logits, mask=jnp.arange(logits.shape[-1]) != networks.mask_id)
        token_log_prob = policy.log_prob(copies["committed"])  # [n, blocks, B]
        in_pass = pass_of[..., None] == jnp.arange(passes)  # [n, blocks, B, S]
        log_prob = jnp.sum(jnp.where(in_pass, token_log_prob[..., None], 0.0), axis=2)
        entropy = policy.entropy().mean()
    return {
        "log_prob": log_prob.reshape(n, steps), "value": value, "entropy": entropy, "stats": stats,
        "positions": copies["clean"].size + copies["noisy"].size,
    }


def sdar_ppo_loss(
    networks: SdarNetworks, params: ActorCriticParams, batch: Dict[str, jax.Array], *,
    clip_eps: float, ent_coef: float, vf_coef: float, aux_coef: float,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The loss on one minibatch of whole sequences (leaves [n, T(, B)]:
    block, commit, token, log_prob, value, advantage, target; prompt [n, B]).
    stoix_tpu/reference/sdar.py::ppo_loss is its plain twin."""
    new = teacher_forced(networks, params, batch)
    stats, positions, entropy = new["stats"], new["positions"], new["entropy"]
    loss_actor = losses.ppo_clip_loss(
        new["log_prob"], batch["log_prob"], batch["advantage"], clip_eps
    )
    value_loss = losses.clipped_value_loss(new["value"], batch["value"], batch["target"], clip_eps)
    layers = stats["expert_count"].shape[0]
    aux_loss = olmoe.load_balancing_loss(stats, positions)
    total = loss_actor - ent_coef * entropy + vf_coef * value_loss + aux_coef * aux_loss
    load = held_counts(stats["expert_count"], networks.held).astype(jnp.float32)
    info = {
        "total_loss": total, "actor_loss": loss_actor, "value_loss": value_loss,
        "entropy": entropy, "aux_loss": aux_loss,
        "expert_load_max_over_mean": jnp.max(load) / jnp.mean(load),
        "router_entropy": jnp.sum(stats["router_entropy_sum"]) / (layers * positions),
        "routed_pairs_per_token": jnp.sum(stats["expert_count"]) / (layers * positions),
        "held_pairs_per_token": jnp.sum(load) / (layers * positions),
    }
    return total, info


class Rollout(NamedTuple):
    key: jax.Array
    env_state: Any
    timestep: Any
    traj: SdarTransition  # leaves [T, E, ...], block-major: step t is pass t % S of block t // S
    prompt: jax.Array  # [E, B]
    routed: jax.Array  # [layers, experts] routed pairs of every model pass
    confidence: jax.Array  # [T, E] mean confidence of each step's commit set


def rollout(
    env: envs.Environment, networks: SdarNetworks, params: ActorCriticParams, key: jax.Array,
    env_state: Any, timestep: Any,
) -> Rollout:
    """One whole episode of every sequence from a reset: the prompt block's
    commit pass, then a scan over the response blocks — S denoise passes (one
    env step each) and the finished block's commit pass."""
    passes, mask_id = networks.passes, networks.mask_id
    size, num_blocks = env.block_length, env.num_blocks  # the env's own Python ints
    count = size // passes

    def commit(cache: Any, tokens: jax.Array, block: Any):
        with annotate(SCOPES["block_commit"]):
            _, cache, stats = networks.block_step(params.actor_params, cache, tokens, block, True)
        return cache, stats["expert_count"]

    def one_block(carry: Tuple, block_index: jax.Array):
        key, env_state, timestep, cache, routed = carry
        block = jnp.full(timestep.reward.shape + (size,), mask_id, jnp.int32)
        steps = []
        for _ in range(passes):
            key, policy_key = jax.random.split(key)
            with annotate(SCOPES["rollout_policy"]), annotate(SCOPES["denoise"]):
                hidden, _, stats = networks.block_step(
                    params.actor_params, cache, block, block_index, False
                )
                value = block_value(networks, params.critic_params, hidden)
                logits = networks.head(params.actor_params, hidden)
                with annotate(SCOPES["lm_head"]):
                    choice = choose(logits, block, mask_id, count, policy_key)
            with annotate(SCOPES["rollout_env"]):
                env_state, timestep = env.step(env_state, choice.block)
            steps.append((
                SdarTransition(
                    block=block, commit=choice.commit, token=choice.token,
                    log_prob=choice.log_prob, value=value, reward=timestep.reward,
                    done=timestep.discount == 0.0, info=timestep.extras["episode_metrics"],
                ),
                choice.confidence,
            ))
            routed = routed + stats["expert_count"]
            block = choice.block
        cache, counts = commit(cache, block, block_index)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *steps)
        return (key, env_state, timestep, cache, routed + counts), stacked

    # The scope is on the scan, not on its body: the loop op itself then
    # carries it, and with it the grouped-matmul kernels inside.
    with annotate(SCOPES["rollout"]):
        cache = networks.init_cache(timestep.reward.shape[0])
        prompt = timestep.observation.agent_view[..., size:2 * size]
        cache, routed = commit(cache, prompt, 0)
        (key, env_state, timestep, _, routed), (traj, confidence) = jax.lax.scan(
            one_block, (key, env_state, timestep, cache, routed),
            1 + jnp.arange(num_blocks, dtype=jnp.int32),
        )
    # [blocks, S, E, ...] -> [T, E, ...]
    flat = lambda x: x.reshape((num_blocks * passes,) + x.shape[2:])
    return Rollout(
        key, env_state, timestep, jax.tree.map(flat, traj), prompt, routed, flat(confidence)
    )


def get_learner_fn(
    env: envs.Environment, networks: SdarNetworks, update_fns: Tuple[Callable, Callable],
    config: Any,
) -> Callable[[LMPPOLearnerState], ExperimentOutput]:
    """The PER-SHARD learner function (wrapped in shard_map by the set-up)."""
    actor_update, critic_update = update_fns
    gamma = float(config.system.gamma)
    gae_lambda = float(config.system.gae_lambda)
    hyper = dict(
        clip_eps=float(config.system.clip_eps), ent_coef=float(config.system.ent_coef),
        vf_coef=float(config.system.vf_coef), aux_coef=float(config.system.router_aux_loss_coef),
    )
    passes = networks.passes
    size, num_blocks = int(env.block_length), int(env.num_blocks)

    @annotate(SCOPES["update_minibatch"])
    def _update_minibatch(train_state: Tuple, batch: Dict[str, jax.Array]):
        params, opt_states = train_state
        grads, info = jax.grad(sdar_ppo_loss, argnums=1, has_aux=True)(
            networks, params, batch, **hyper
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
        key, env_state, timestep, traj, prompt, routed, confidence = rollout(
            env, networks, params, learner_state.key, learner_state.env_state,
            learner_state.timestep,
        )

        with annotate(SCOPES["gae"]):
            # Every rollout is one whole episode: the last step terminates.
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

        # [T, E, ...] -> [E, 1, T, ...]: a sample of the shuffle is one whole
        # sequence, and a minibatch arrives as [sequences, T, ...].
        data = {
            "block": traj.block, "commit": traj.commit, "token": traj.token,
            "log_prob": traj.log_prob, "value": traj.value, "advantage": advantages,
            "target": targets,
        }
        data = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1)[:, None], data)
        data["prompt"] = prompt[:, None]
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
        # The rollout's counters: one commit pass for the prompt, then S
        # denoise passes and one commit pass a block, B positions a pass.
        model_passes = 1 + num_blocks * (passes + 1)
        positions = model_passes * size * traj.reward.shape[1] * routed.shape[0]
        tokens = num_blocks * size
        held = jnp.sum(held_counts(routed, networks.held))
        info.update({
            "rollout_routed_pairs_per_token": jnp.sum(routed).astype(jnp.float32) / positions,
            "rollout_held_pairs_per_token": held.astype(jnp.float32) / positions,
            "decode_passes_per_token": jnp.float32(model_passes / tokens),
            "tokens_per_denoise_pass": jnp.float32(tokens / (num_blocks * passes)),
            "commit_confidence_mean": jnp.mean(confidence),
        })
        record = {
            "rollout_block": traj.block, "rollout_commit": traj.commit, "rollout_token": traj.token,
            "rollout_log_prob": traj.log_prob, "rollout_value": traj.value,
        }
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


def build_networks(env: envs.Environment, config: Any) -> Tuple[sdar.SdarLM, olmoe.ValueHead]:
    """(trunk + head, value head) from the network config; the vocabulary
    slice and the block length are the env's. The seam a harness wraps."""
    net_cfg = config.network
    actor = config_lib.instantiate(
        net_cfg.actor_network, vocab_size=int(env.vocab_size), block_length=int(env.block_length)
    )
    critic = config_lib.instantiate(net_cfg.critic_network)
    return actor, critic


def network_functions(
    actor: sdar.SdarLM, critic: olmoe.ValueHead, max_len: int, passes: int
) -> SdarNetworks:
    return SdarNetworks(
        block_step=actor.block_step,
        trunk_copies=actor.trunk_copies,
        head=actor.head,
        value=critic.apply,
        init_cache=lambda batch: actor.init_cache(batch, max_len),
        mask_id=actor.vocab_size - 1,
        passes=passes,
        held=actor.held,
    )


def _update_attention_gauge() -> Any:
    return get_registry().gauge(
        "stoix_tpu_sdar_update_attention",
        "the block-diffusion update's attention as the learner was set up, by field: kernel "
        "(1 = the Pallas kernel over the block mask, 0 = plain masked products), "
        "tiles_visited and tiles_total (128 x 128 tiles of (query, key) positions with an "
        "allowed pair, and all)",
    )


def _norm_rope_gauge() -> Any:
    return get_registry().gauge(
        "stoix_tpu_qk_norm_rope",
        "1 on the form q's and k's per-head norm and rotation take in the update's "
        "teacher-forced pass as the learner was set up, 0 on the other: kernel (one Pallas "
        "pass each way over the projection's rows, ops/qk_norm_rope.py) or plain (rms_norm + "
        "rope as XLA compiles them)",
    )


def sequence_length(env: envs.Environment) -> int:
    """Positions of one sequence: the prompt block and the response."""
    return int(env.block_length) + int(env.length)


def make_init_state(
    env: envs.Environment, config: Any, actor: sdar.SdarLM, critic: olmoe.ValueHead,
    optims: Tuple[Any, Any], n_shards: int,
) -> Callable[[jax.Array], LMPPOLearnerState]:
    """key -> the whole initial learner state, as one traceable function."""
    actor_optim, critic_optim = optims

    def init_state(key: jax.Array) -> LMPPOLearnerState:
        key, actor_key, critic_key, env_key = jax.random.split(key, 4)
        actor_params = actor.init(actor_key)
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


def make_act_fn(networks: SdarNetworks, size: int, greedy: bool) -> Callable:
    """The evaluator's batched step through the same `block_step`: the
    observation says which block and pass every episode is at (they move in
    lockstep); the prompt is committed at the first call, a finished block
    at every S-th."""
    passes, mask_id = networks.passes, networks.mask_id

    def commit(params: Any, cache: Any, tokens: jax.Array, block: jax.Array):
        with annotate(SCOPES["block_commit"]):
            return networks.block_step(params, cache, tokens, block, True)[1]

    def act_fn(params: Any, cache: Any, observation: Any, done: jax.Array, keys: jax.Array):
        view = observation.agent_view
        block, prompt = view[:, :size], view[:, size:2 * size]
        block_index, pass_index = view[0, 2 * size] + 1, view[0, 2 * size + 1]
        cache = jax.lax.cond(
            observation.step_count[0] == 0,
            lambda c: commit(params, c, prompt, jnp.int32(0)), lambda c: c, cache,
        )
        with annotate(SCOPES["denoise"]):
            hidden, _, _ = networks.block_step(params, cache, block, block_index, False)
            logits = networks.head(params, hidden)
            with annotate(SCOPES["lm_head"]):
                choice = choose(logits, block, mask_id, size // passes, None if greedy else keys[0])
        cache = jax.lax.cond(
            pass_index == passes - 1,
            lambda c: commit(params, c, choice.block, block_index), lambda c: c, cache,
        )
        return cache, choice.block

    return act_fn


def learner_setup(env: envs.Environment, config: Any, mesh: Mesh, key: jax.Array) -> AnakinSetup:
    rollout_length = int(config.system.rollout_length)
    if int(env.episode_steps) != rollout_length:
        raise ValueError(
            f"ff_sdar_ppo needs the env's steps an episode ({int(env.episode_steps)} = "
            f"{int(env.num_blocks)} blocks x {int(env.passes)} passes) to equal "
            f"system.rollout_length ({rollout_length}): every rollout is one whole sequence "
            "from an empty cache"
        )
    if int(config.arch.get("update_batch_size", 1)) != 1:
        raise ValueError(
            "ff_sdar_ppo has no in-shard replica axis: arch.update_batch_size must be 1"
        )
    n_shards = int(mesh.shape["data"])
    envs_per_shard = int(config.arch.total_num_envs) // n_shards
    if envs_per_shard % int(config.system.num_minibatches) != 0:
        raise ValueError(
            f"{envs_per_shard} sequences a shard do not divide into "
            f"system.num_minibatches={int(config.system.num_minibatches)} minibatches of whole "
            "sequences"
        )
    config.system.action_dim = int(env.vocab_size)

    actor, critic = build_networks(env, config)
    networks = network_functions(actor, critic, sequence_length(env), int(env.passes))
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

    # The state is built ON its shardings by one jitted program: parameters
    # and two Adam moments are never held twice.
    with span("network_init"):
        out_shardings = LMPPOLearnerState(*(
            jax.tree.map(lambda _: NamedSharding(mesh, spec), field)
            for field, spec in zip(jax.eval_shape(init_state, key), state_specs)
        ))
        learner_state = jax.jit(init_state, out_shardings=out_shardings)(key)

    learn_per_shard = get_learner_fn(
        env, networks, (actor_optim.update, critic_optim.update), config
    )
    learn = anakin.shardmap_learner(
        learn_per_shard, mesh, state_specs, episode_metrics_spec=P(None, None, "data")
    )

    # How the update multiplies its attention scores (decided by what the
    # network sees of the backend and the shapes), for the run's record.
    for field, value in actor.copies_attention(sequence_length(env), int(env.passes)).items():
        _update_attention_gauge().set(value, {"field": field})
    update_positions = sequence_length(env) + int(env.passes) * int(env.length)
    for form in ("kernel", "plain"):
        taken = norm_rope_form(update_positions, actor.head_dim) == form
        _norm_rope_gauge().set(float(taken), {"form": form})
    # ... and the held experts' SwiGLU in a denoise pass over a block of every sequence.
    held_form = set_held_swiglu_gauge(actor, envs_per_shard * int(env.block_length))

    if is_coordinator():
        get_logger("stoix_tpu.setup").info(
            "[setup] %s parameters | mesh %s | %s sequences x %s blocks of %s tokens, %s passes a "
            "block | experts %s..%s of %s held, their SwiGLU of a pass: %s",
            f"{count_parameters(learner_state.params):,}", dict(mesh.shape),
            config.arch.total_num_envs, int(env.num_blocks), int(env.block_length),
            int(env.passes), actor.held[0], sum(actor.held) - 1, actor.num_experts, held_form,
        )

    return AnakinSetup(
        learn=learn,
        learner_state=learner_state,
        eval_act_fn=make_act_fn(
            networks, int(env.block_length), bool(config.arch.get("evaluation_greedy", False))
        ),
        # The live actor parameters themselves, not a copy.
        eval_params_fn=lambda s: s.params.actor_params,
    )


def run_experiment(config: Any) -> float:
    """Train; returns the final evaluation episode-return mean."""
    net, env_kwargs = config.network.actor_network, config.env.kwargs
    max_len = int(env_kwargs.block_length) + int(env_kwargs.length)
    init_cache = lambda batch: sdar.init_cache(
        int(net.get("num_layers", 1)), batch, max_len, int(net.num_kv_heads), int(net.head_dim)
    )
    final_return = run_anakin_experiment(
        config, learner_setup,
        evaluator_setup_fn=carry_evaluator_setup(init_cache),
    )
    LAST_RUN_STATS["update_attention"] = {
        dict(labels)["field"]: int(value)
        for labels, value in _update_attention_gauge().labels_and_values()
    }
    return final_return


def main() -> float:
    import sys

    config = config_lib.compose(
        config_lib.default_config_dir(), "default/anakin/default_ff_sdar_ppo.yaml", sys.argv[1:]
    )
    return run_experiment(config)


if __name__ == "__main__":
    main()
