"""Shuhai core: the paper's contribution as a composable library.

Public surface:
  RSTParams, EngineRegisters        — runtime parameters (Table I) + packing
  addresses_np / addresses_jnp      — Eq. 1 address streams
  AddressMapping, get_mapping       — Table II policies (registrable:
                                      register_policies)
  serial_read_latencies, throughput — the calibrated timing model
  contended_throughput              — N engines sharing one channel port
                                      (ARBITRATION_POLICIES grant axis)
  Engine, Backend                   — engines + pluggable measurement
                                      backends (register_backend);
                                      PLACEMENTS routes cross-channel
                                      contention, UnsupportedCapability
                                      marks missing backend abilities
  MemorySpec, register_spec         — registrable memory systems; HBM/DDR4
                                      (measured) + HBM3/DDR3 (modeled)
  Experiment, run_experiment        — declarative paper-artifact registry
                                      (+ write/duplex family, catalog)
  ShuhaiCampaign                    — deprecated suite shims over the registry
  Sweep                             — batch-first campaign grids (memoized)
  SwitchModel, SwitchTopology       — Sec. II / VI switch + parametric
                                      fabrics (register_topology)
  MemoryOracle, AccessPattern       — TPU-facing constants + derating
  choose_layout, advise_microbatch  — the technique as a framework feature
"""
from repro.core.address_mapping import (AddressMapping, get_mapping,
                                        policies_for, register_policies)
from repro.core.autotune import (LayoutCandidate, LayoutConfig, LayoutTuner,
                                 TuneReport, TuneRound, advise_microbatch,
                                 advise_remat, choose_layout, score_layouts,
                                 tune_layout)
from repro.core.bench_host import ShuhaiCampaign, default_campaigns
from repro.core.channels import (CrossingLatencyTable, DDR4Topology,
                                 HBMTopology, SwitchTopology,
                                 available_topologies, flat_topology,
                                 register_topology, topology_for)
from repro.core.engine import (Backend, Engine, UnsupportedCapability,
                               available_backends, get_backend,
                               register_backend)
from repro.core.experiments import (Experiment, all_experiments,
                                    experiments_for, get_experiment,
                                    register_experiment, run_experiment)
from repro.core.hwspec import (DDR3, DDR4, HBM, HBM3, TPU_V5E, ChipSpec,
                               MemorySpec, available_chips, available_specs,
                               chip_by_name, chip_for_device, register_chip,
                               register_spec, spec_by_name)
from repro.core.latency import LatencyModule
from repro.core.oracle import AccessPattern, MemoryOracle
from repro.core.params import EngineRegisters, RSTParams
from repro.core.roofline_empirical import (EnvelopePoint, RooflineEnvelope,
                                           build_envelope,
                                           config_ceiling_gbps,
                                           measure_envelope)
from repro.core.rst import addresses_jnp, addresses_np, block_params
from repro.core.sweep import Sweep, SweepPoint, SweepResult
from repro.core.switch import PLACEMENTS, SwitchModel
from repro.core.timing_model import (ARBITRATION_POLICIES, ContentionResult,
                                     LatencyTrace, ThroughputResult,
                                     contended_throughput,
                                     refresh_interval_estimate,
                                     serial_latencies, serial_read_latencies,
                                     throughput)

__all__ = [
    "AddressMapping", "get_mapping", "policies_for", "register_policies",
    "LayoutCandidate", "LayoutConfig", "LayoutTuner", "TuneReport",
    "TuneRound", "advise_microbatch", "advise_remat", "choose_layout",
    "score_layouts", "tune_layout",
    "EnvelopePoint", "RooflineEnvelope", "build_envelope",
    "config_ceiling_gbps", "measure_envelope",
    "ShuhaiCampaign", "default_campaigns",
    "CrossingLatencyTable", "DDR4Topology", "HBMTopology", "SwitchTopology",
    "available_topologies", "flat_topology", "register_topology",
    "topology_for",
    "Backend", "Engine", "UnsupportedCapability", "available_backends",
    "get_backend", "register_backend",
    "Experiment", "all_experiments", "experiments_for", "get_experiment",
    "register_experiment", "run_experiment",
    "DDR3", "DDR4", "HBM", "HBM3", "TPU_V5E", "ChipSpec", "MemorySpec",
    "available_chips", "available_specs", "chip_by_name", "chip_for_device",
    "register_chip", "register_spec", "spec_by_name",
    "LatencyModule", "AccessPattern", "MemoryOracle",
    "EngineRegisters", "RSTParams",
    "addresses_jnp", "addresses_np", "block_params",
    "Sweep", "SweepPoint", "SweepResult",
    "SwitchModel", "LatencyTrace", "ThroughputResult", "ContentionResult",
    "ARBITRATION_POLICIES", "PLACEMENTS",
    "contended_throughput", "refresh_interval_estimate", "serial_latencies",
    "serial_read_latencies", "throughput",
]
