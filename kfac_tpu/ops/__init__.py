"""Numerics: covariance factors and second-order linear algebra."""

from kfac_tpu.ops import cov, factors, pallas_ns

__all__ = ['cov', 'factors', 'pallas_ns']
