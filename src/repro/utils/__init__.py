"""Small shared utilities: pytree algebra (stack/index/mean/mix — the
federation's stacked-leaf operations) and RNG helpers."""
from repro.utils.pytree import (
    tree_vector_size,
    tree_to_vector,
    vector_to_tree,
    tree_stack,
    tree_unstack,
    tree_index,
    tree_scale,
    tree_add,
    tree_sub,
    tree_zeros_like,
    tree_l2_norm,
    tree_mean,
    tree_weighted_mix,
    tree_map_with_path_names,
)
from repro.utils.rng import key_iter, split_like
