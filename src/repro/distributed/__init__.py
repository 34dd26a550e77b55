from repro.distributed.sharding import (
    BLOCK_AXIS,
    Rules,
    block_shard_count,
    block_sharding,
    block_specs,
    current_rules,
    install_rules,
    make_block_mesh,
    param_shardings,
    shard_act,
    use_rules,
)
