"""Operation counts from shapes, one module per ``kind``:
``benchmark/flops/<kind>.py`` exposes ``train_flops_per_sample(config)``,
the forward and backward passes' floating-point operations for one sample
(an image, or a sequence of the configuration's length). K-FAC's own work
and recomputation are not in it: it is the numerator of ``mfu``.
"""
