"""repro — Mandator & Sporades as a tick-level WAN-consensus simulator in JAX."""

__version__ = "1.0.0"
