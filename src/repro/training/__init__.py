from .steps import (  # noqa: F401
    init_train_state,
    init_weights,
    make_algo,
    make_prune_fn,
    make_rigl_step,
    make_train_step,
    refresh_pack,
    snip_init,
    sparsity_map,
)
