"""Candidate publishers — the trainer's half of the release loop.

A publisher turns the live training scope into a versioned artifact in
the model store, through the crash-safe staged publish
(``fluid.io.publish_model_version``): the trainer can be SIGKILLed at
any instruction and the store holds either the complete version or no
version — never a torn artifact for ``ModelRegistry.load``.

Two artifact shapes, matching what the registry serves:

* ``CandidatePublisher`` — a ``save_versioned_inference_model`` engine
  artifact (batch inference through ``InferenceEngine``); with
  ``int8=True`` the version ships a ``gateway.json`` manifest asking
  the registry to run the PR 7 per-channel PTQ at load
  (``quantize="int8"``), so the deployable artifact stays fp32 on disk
  and the int8 rewrite happens against the loaded copy.
* ``GeneratorPublisher`` — a paged-generator artifact
  (``ModelRegistry.save_generator_artifact``): trained weights are
  snapshotted into a serving clone via ``copy_weights`` under the PR 5
  ``param_prefix`` naming contract, so the trainer's scope and the
  decode programs agree on every parameter name.  ``kv_dtype="int8"``
  in the generator config publishes the block-scaled int8-KV server.

Both are duck-typed to the ``ResilientTrainer`` hook:
``publish(step, program=None, scope=None) -> version``.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from ..fluid import io as fio

__all__ = ["CandidatePublisher", "GeneratorPublisher"]


class CandidatePublisher:
    """Versioned engine-artifact publisher for a live training scope."""

    def __init__(self, root: str, name: str, feed_names: List[str],
                 target_vars, executor, main_program=None, scope=None,
                 int8: bool = False,
                 version_fn: Optional[Callable[[int], str]] = None):
        self.root = str(root)
        self.name = str(name)
        self.feed_names = list(feed_names)
        self.target_vars = list(target_vars)
        self.executor = executor
        self.main_program = main_program
        self.scope = scope
        self.int8 = bool(int8)
        self.version_fn = version_fn or str

    def manifest(self) -> Optional[Dict]:
        if not self.int8:
            return None
        return {"kind": "engine", "config": {"quantize": "int8"}}

    def publish(self, step: int, program=None, scope=None) -> str:
        version = str(self.version_fn(int(step)))
        fio.save_versioned_inference_model(
            self.root, self.name, version, self.feed_names,
            self.target_vars, self.executor,
            main_program=program or self.main_program,
            scope=scope or self.scope, manifest=self.manifest())
        return version


class GeneratorPublisher:
    """Paged-generator artifact publisher: snapshot the trained
    parameters into a serving clone, publish the clone's persistables
    plus its constructor manifest as one atomic version."""

    def __init__(self, root: str, name: str, generator_config: Dict,
                 scope=None, place=None,
                 version_fn: Optional[Callable[[int], str]] = None):
        self.root = str(root)
        self.name = str(name)
        # the PagedTransformerGenerator constructor surface (the same
        # keys a gateway.json manifest carries) — validated by the
        # generator itself at first publish
        self.generator_config = dict(generator_config)
        self.scope = scope
        self.place = place
        self.version_fn = version_fn or str
        self._gen = None            # built lazily: one clone, reused

    def _generator(self):
        if self._gen is None:
            from ..serving import PagedTransformerGenerator

            self._gen = PagedTransformerGenerator(
                place=self.place, **self.generator_config)
        return self._gen

    def publish(self, step: int, program=None, scope=None) -> str:
        from ..serving import copy_weights
        from ..serving.gateway import ModelRegistry

        version = str(self.version_fn(int(step)))
        gen = self._generator()
        src_scope = scope or self.scope
        if src_scope is None:
            raise ValueError("GeneratorPublisher.publish: no scope "
                             "(pass one at construction or publish)")
        copy_weights(src_scope, gen.scope,
                     prefix=self.generator_config.get("param_prefix"))
        ModelRegistry.save_generator_artifact(
            gen, self.root, self.name, version)
        return version
