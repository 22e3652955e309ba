from repro.parallel.axes import (  # noqa: F401
    DEFAULT_RULES, axis_rules, current_mesh, current_rules, logical_to_spec,
    shard,
)
