"""Public ops API — the one import surface for systems and networks.

Systems import estimators/losses/kernels from HERE (`from stoix_tpu.ops
import truncated_generalized_advantage_estimation, losses`) rather than
deep module paths, so the package layout can evolve (the scan-kernel
dispatch behind the multistep estimators is exactly such an evolution)
without touching thirty call sites. The submodules stay importable for
internal use and tests.
"""

from stoix_tpu.ops import (
    distributions,
    losses,
    minibatch,
    multistep,
    pallas_attention,
    ring_attention,
    running_statistics,
    scan_kernels,
    value_transforms,
)
from stoix_tpu.ops.distributions import Distribution, EpsilonGreedy, pick_along_last
from stoix_tpu.ops.losses import categorical_l2_project
from stoix_tpu.ops.minibatch import shuffled_minibatch_epoch
from stoix_tpu.ops.multistep import (
    batch_discounted_returns,
    batch_general_off_policy_returns_from_q_and_v,
    batch_lambda_returns,
    batch_n_step_bootstrapped_returns,
    batch_q_lambda,
    batch_retrace_continuous,
    batch_truncated_generalized_advantage_estimation,
    discounted_returns,
    general_off_policy_returns_from_q_and_v,
    importance_corrected_td_errors,
    lambda_returns,
    n_step_bootstrapped_returns,
    q_lambda,
    retrace_continuous,
    truncated_generalized_advantage_estimation,
    vtrace_td_error_and_advantage,
)
from stoix_tpu.ops.pallas_attention import best_attention, flash_attention
from stoix_tpu.ops.ring_attention import full_attention, make_ring_attention
from stoix_tpu.ops.scan_kernels import (
    VALID_IMPLS,
    affine_window_fold,
    linear_recurrence_reverse,
    pallas_linear_recurrence_reverse,
)
from stoix_tpu.ops.value_transforms import (
    IDENTITY_PAIR,
    SIGNED_HYPERBOLIC_PAIR,
    TxPair,
    muzero_pair,
    signed_hyperbolic,
    signed_parabolic,
    transformed_n_step_q_learning_td,
    twohot,
)

__all__ = [
    # submodules
    "distributions",
    "losses",
    "minibatch",
    "multistep",
    "pallas_attention",
    "ring_attention",
    "running_statistics",
    "scan_kernels",
    "value_transforms",
    # multistep estimators (+ batched aliases)
    "batch_discounted_returns",
    "batch_general_off_policy_returns_from_q_and_v",
    "batch_lambda_returns",
    "batch_n_step_bootstrapped_returns",
    "batch_q_lambda",
    "batch_retrace_continuous",
    "batch_truncated_generalized_advantage_estimation",
    "discounted_returns",
    "general_off_policy_returns_from_q_and_v",
    "importance_corrected_td_errors",
    "lambda_returns",
    "n_step_bootstrapped_returns",
    "q_lambda",
    "retrace_continuous",
    "truncated_generalized_advantage_estimation",
    "vtrace_td_error_and_advantage",
    # scan kernels
    "VALID_IMPLS",
    "affine_window_fold",
    "linear_recurrence_reverse",
    "pallas_linear_recurrence_reverse",
    # attention entry points
    "best_attention",
    "flash_attention",
    "full_attention",
    "make_ring_attention",
    # value transforms
    "IDENTITY_PAIR",
    "SIGNED_HYPERBOLIC_PAIR",
    "TxPair",
    "muzero_pair",
    "signed_hyperbolic",
    "signed_parabolic",
    "transformed_n_step_q_learning_td",
    "twohot",
    # losses commonly imported by name (distributional projection)
    "categorical_l2_project",
    # the PPO learners' shuffled epoch (pack once, one gather a minibatch)
    "shuffled_minibatch_epoch",
    # distributions commonly referenced by name
    "Distribution",
    "EpsilonGreedy",
    # x[..., index] along the last axis: by select where it is narrow
    "pick_along_last",
]
