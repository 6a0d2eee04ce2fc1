"""Loop-aware HLO cost analysis (``hlo_cost``) and the static hot-path
contract checker (``python -m repro.analysis.check``): HLO/jaxpr lint
rules (``contracts``), Pallas VMEM budget estimation (``vmem``) and the
mirror-coherence AST lint (``mirror_lint``)."""
