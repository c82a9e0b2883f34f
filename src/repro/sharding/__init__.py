from .serve import (
    DECODE_WEIGHT_AXES,
    ROW_SHARDED_WEIGHTS,
    ServeMesh,
    shard_block_tables,
    validate_serve_mesh,
)
from .specs import (
    MeshRules,
    auto_mesh,
    current_rules,
    logical_to_spec,
    set_rules,
    shard_act,
    use_rules,
)
