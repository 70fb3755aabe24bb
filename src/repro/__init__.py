"""repro — FlashDecoding++ on TPU: a JAX + Pallas training/inference framework."""

__version__ = "0.1.0"
