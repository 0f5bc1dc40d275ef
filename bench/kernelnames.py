"""Names by which the device trace shows the region-sweep kernels.

The trace names each device op by its HLO text; the Pallas sweeps (the
VMEM-resident and the HBM-streamed one) are the custom call named after
the jitted ``level_sweep`` that wraps them, e.g.
``%level_sweep.1 = s8[11,16,9216]... custom-call(...)``.
"""

SWEEP_KERNELS = ("%level_sweep",)
