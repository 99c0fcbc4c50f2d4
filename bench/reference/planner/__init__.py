"""A plain reference for the planner: the paper's parameter-optimization
problem and its NumPy GIA solver, copied from the program so that the
benchmark's answers are checked by code that later program changes cannot
touch.  It covers the GenQSGD family, full participation, no faults, and
the fixed step-size rules (constant, exponential, diminishing).
"""
from .cost import EdgeSystem
from .convergence import MLProblemConstants
from .gia import GIAResult, solve_param_opt
from .problems import Objective, ParamOptProblem

__all__ = ["EdgeSystem", "MLProblemConstants", "GIAResult",
           "solve_param_opt", "Objective", "ParamOptProblem"]
