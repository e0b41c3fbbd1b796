"""FedCAMS (Communication-Efficient Adaptive Federated Learning, ICML 2022)
as a production multi-pod JAX/Pallas framework.

Public surface:
    repro.core     — FedAMS/FedCAMS, compressors, error feedback, rounds,
                     FederatedTrainer facade
    repro.comm     — wire formats & transport (see below)
    repro.models   — the six-family architecture substrate (Model)
    repro.configs  — the 10 assigned architecture configs + dataclasses
    repro.kernels  — Pallas TPU kernels (+ jnp oracles)
    repro.launch   — production mesh, dry-run, train/serve drivers

Wire formats & transport (repro.comm):
    The paper accounts communication analytically (Table 1 bits);
    ``repro.comm`` makes it physical. ``comm.wire`` packs each compressed
    delta into an actual byte buffer — dense fp32, top-k (uint32 index +
    fp32/fp16/bf16 value), block-top-k (log2(B)-bit packed indices, optional
    int8 values against per-block scales) and sign (1 bit/coord + fp32
    scale) — decoding bit-exactly back to the dense compressor output.
    ``comm.transport`` moves those bytes through a simulated client fleet
    with per-client asymmetric bandwidth, latency jitter and stragglers.
    ``FedConfig(wire=True)`` routes every FedSim round through
    encode→transport→decode (two-way compression exercises the downlink
    codec too) and surfaces measured ``wire_bytes`` / ``round_time_s`` into
    ``FederatedTrainer.history``; ``kernels.bitpack`` holds the word-wise
    sub-word packing the codecs use, and ``benchmarks/bench_wire.py`` measures codec throughput and
    measured-vs-analytic bytes.
"""

__version__ = "1.0.0"
