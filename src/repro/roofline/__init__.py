from repro.roofline import hlo
from repro.roofline.hlo import collective_phase_analysis

__all__ = ["hlo", "collective_phase_analysis"]
