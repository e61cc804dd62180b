"""Fleet sweeps: every LM config through the batched analytical engine.

``extract`` walks the model configs into parameter-exact per-layer
matmul workloads (prefill/decode, optionally sharded to per-device
shapes under the production mesh); ``sweep`` routes the whole fleet x
sparsity-option portfolio through shared compiled programs in
O(#options) compiles; ``validate`` checks the advisor's verdict signs
against measured Pallas kernels on the REDUCED configs.
"""
from .extract import (LayerMatmul, MeshSpec, NetworkWorkloads,
                      extract_fleet, extract_network,
                      production_mesh_spec, shard_entries)
from .sweep import (FleetReport, LayerVerdict, SweepOption,
                    default_options, dedupe_shapes, dsa_option,
                    fleet_sweep,
                    nm_design_for_weights, nm_option)

__all__ = [
    "LayerMatmul", "MeshSpec", "NetworkWorkloads", "extract_fleet",
    "extract_network", "production_mesh_spec", "shard_entries",
    "FleetReport", "LayerVerdict", "SweepOption", "default_options",
    "dedupe_shapes", "dsa_option", "fleet_sweep", "nm_design_for_weights",
    "nm_option",
]
