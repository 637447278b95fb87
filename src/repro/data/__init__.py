from repro.data import synthetic
from repro.data.synthetic import paper_dataset, paper_dataset_names

__all__ = ["synthetic", "paper_dataset", "paper_dataset_names"]
