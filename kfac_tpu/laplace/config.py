"""Configuration for KFAC-Laplace posterior export and serving.

The knobs here are the ONLY serving-time parameters of an exported
posterior; everything else (eigenbases, eigenvalues, MAP weights) is
frozen into the artifact at export time. ``prior_precision`` and
``temperature`` enter the sampling/variance formulas at serve time, so
they can be refit on held-out data (:func:`kfac_tpu.laplace
.fit_prior_precision`) without re-exporting.

The knob table in docs/LAPLACE.md is pinned to these fields by the
KFL107 drift rule (kfac_tpu/analysis/drift.py) — the same doc-vs-code
contract as the calibration (KFL108) and chaos (KFL111) knob tables.
"""

from __future__ import annotations

import dataclasses

#: supported posterior structures, in docs order
MODES = ('kron', 'diag', 'last_layer')


@dataclasses.dataclass(frozen=True)
class LaplaceConfig:
    """Knobs for :func:`kfac_tpu.laplace.export_posterior`.

    Attributes:
        mode: posterior structure. ``'kron'`` is the full KFAC-Laplace
            (Ritter et al. 2018): per-layer Kronecker-factored Gaussian
            over ALL registered layers, sampled through the factor
            eigenbases. ``'diag'`` keeps only the factor diagonals —
            a diagonal-Kronecker Gaussian in parameter coordinates,
            (a_dim + g_dim) floats per layer instead of two dense bases.
            ``'last_layer'`` is the linearized last-layer Laplace: kron
            structure over ONE layer (every other layer stays MAP), with
            a closed-form predictive-variance path that needs no
            sampling.
        prior_precision: isotropic Gaussian prior precision ``p`` added
            to the curvature. Enters Kronecker-wise as ``sqrt(p)`` per
            factor so the composed precision is ``H + p I`` up to the
            usual cross terms. Fit it on held-out data with
            :func:`kfac_tpu.laplace.fit_prior_precision` rather than
            hand-tuning.
        temperature: posterior sharpening ``T``: sample covariance is
            scaled by ``T`` (``T < 1`` concentrates toward MAP, the
            cold-posterior regime; ``T = 1`` is the Laplace posterior).
        last_layer: registered layer name the ``'last_layer'`` mode
            covers. ``None`` picks the LAST registered layer
            (registration order follows model execution order).
        n_samples: default Monte-Carlo sample count for
            :meth:`~kfac_tpu.laplace.LaplacePosterior.predictive`.
    """

    mode: str = 'kron'
    prior_precision: float = 1.0
    temperature: float = 1.0
    last_layer: str | None = None
    n_samples: int = 30

    def __post_init__(self) -> None:
        if self.mode not in MODES:
            raise ValueError(
                f'LaplaceConfig.mode must be one of {MODES}, '
                f'got {self.mode!r}'
            )
        if self.prior_precision <= 0:
            raise ValueError(
                'LaplaceConfig.prior_precision must be positive (it is a '
                f'Gaussian prior precision), got {self.prior_precision}'
            )
        if self.temperature <= 0:
            raise ValueError(
                'LaplaceConfig.temperature must be positive, '
                f'got {self.temperature}'
            )
        if self.n_samples < 1:
            raise ValueError(
                f'LaplaceConfig.n_samples must be >= 1, got {self.n_samples}'
            )
        if self.last_layer is not None and self.mode != 'last_layer':
            raise ValueError(
                "LaplaceConfig.last_layer only applies to mode='last_layer' "
                f'(got mode={self.mode!r})'
            )
