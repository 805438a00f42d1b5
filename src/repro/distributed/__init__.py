from repro.distributed.sharding import (param_shardings, cache_shardings,
                                        batch_spec, ShardingRules)
from repro.distributed.cp_retrieval import cp_partial_verify_attention
from repro.distributed.cp_verify import (cp_full_verify_attention,
                                         psum_softmax_merge,
                                         merged_partials_bytes,
                                         gathered_blocks_bytes,
                                         verify_traffic_report)

__all__ = ["param_shardings", "cache_shardings", "batch_spec",
           "ShardingRules", "cp_partial_verify_attention",
           "cp_full_verify_attention", "psum_softmax_merge",
           "merged_partials_bytes", "gathered_blocks_bytes",
           "verify_traffic_report"]
