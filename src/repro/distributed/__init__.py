"""Distribution layer: sharding rules, overlapped collectives, placement."""

from .mesh_utils import batch_pref, data_axes, named, ranks_mesh, \
    ring_perm, valid_spec
from .sharding_rules import ShardingRules
from .transport import (BucketPolicy, CompileProbe, HostTransport,
                        ProgramCache, ResidentBuffers, ShipSlots,
                        TransferProbe, Transport, make_transport,
                        next_pow2, pack_allgather, pack_rounds)
from .overlap import (allgather_matmul, allgather_matmul_local,
                      matmul_reducescatter, matmul_reducescatter_local)
from .halo import full_window_attention_ref, sp_local_attention, \
    swa_halo_exchange
from .pipeline import assign_stages, layer_costs, place_experts
from .compression import (CompressState, compress_grads, compressed_bytes,
                          decompress_grads, init_compress_state)

__all__ = [
    "batch_pref", "data_axes",
    "named", "ranks_mesh", "ring_perm", "valid_spec", "ShardingRules",
    "BucketPolicy", "CompileProbe", "HostTransport", "ProgramCache",
    "ResidentBuffers", "ShipSlots", "TransferProbe", "Transport",
    "make_transport", "next_pow2", "pack_allgather", "pack_rounds",
    "allgather_matmul", "allgather_matmul_local", "matmul_reducescatter",
    "matmul_reducescatter_local", "full_window_attention_ref",
    "sp_local_attention", "swa_halo_exchange", "assign_stages",
    "layer_costs", "place_experts", "CompressState", "compress_grads",
    "compressed_bytes", "decompress_grads", "init_compress_state",
]
