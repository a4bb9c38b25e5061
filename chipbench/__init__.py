"""On-chip benchmark of the serving and training stack: one command runs one
cell (a model configuration under one traffic mix) on the TPU it is started
on. See ``chipbench/run.py``."""
