"""The plain reference: the scalar, single-cluster discrete-event simulator.

A copy of the program's scalar path (kubernetriks_tpu/{sim,core,autoscalers},
config.py, metrics/collector.py, trace/{interface,generic}.py) as of commit
e797962, with imports rewritten to this package and the chaos-engine branch
of `KubernetriksSimulation.initialize` cut out (no cell injects faults). It
imports nothing of the program, so a PR that changes the program's scalar
path cannot move what the benchmark calls correct. The batched path under
test (kubernetriks_tpu/batched, ops) shares no code with it.
"""
