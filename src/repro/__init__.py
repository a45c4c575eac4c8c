"""repro — Quasi-Global Momentum (Lin et al., ICML 2021) as a production
JAX framework: decentralized optimizers + gossip schedules, ten assigned
architectures, Pallas TPU kernels, training and serving on one or four TPU
chips."""
__version__ = "0.1.0"
