"""Seconds from calling the entry point (`serve.run`, `JaxTrainer.fit`)
until the deployment was ready (replicas built and warmed up; the train
loop at its first line)."""


def read(obs, params):
    return obs.get("ready_s")
