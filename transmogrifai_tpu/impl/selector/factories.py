"""ModelSelector factories (reference:
core/.../impl/classification/BinaryClassificationModelSelector.scala:52-179,
MultiClassificationModelSelector.scala, impl/regression/RegressionModelSelector.scala).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..tuning.splitters import DataBalancer, DataCutter, DataSplitter, Splitter
from ..tuning.validators import OpCrossValidation, OpTrainValidationSplit
from .model_selector import ModelSelector


def _build(problem: str, validator, splitter, models, evaluator):
    return ModelSelector(problem=problem, validator=validator,
                         splitter=splitter, models=models, evaluator=evaluator)


class BinaryClassificationModelSelector:
    """Defaults (reference :52-129): CV 3 folds, AuPR metric, DataBalancer."""

    @staticmethod
    def with_cross_validation(num_folds: int = 3, seed: int = 42,
                              splitter: Optional[Splitter] = None,
                              models: Optional[Sequence[Tuple[Any, Optional[List[Dict]]]]] = None,
                              evaluator=None, stratify: bool = False,
                              **validator_kw) -> ModelSelector:
        # validator_kw passes through to OpCrossValidation — e.g.
        # max_eval_rows=None, exact_sweep_fits=True for reference-exact
        # sweep semantics
        return _build("binary",
                      OpCrossValidation(num_folds=num_folds, seed=seed, stratify=stratify,
                                        **validator_kw),
                      splitter if splitter is not None else DataBalancer(seed=seed),
                      models, evaluator)

    @staticmethod
    def with_train_validation_split(train_ratio: float = 0.75, seed: int = 42,
                                    splitter: Optional[Splitter] = None,
                                    models=None, evaluator=None,
                                    stratify: bool = False,
                                    **validator_kw) -> ModelSelector:
        return _build("binary",
                      OpTrainValidationSplit(train_ratio=train_ratio, seed=seed,
                                             stratify=stratify, **validator_kw),
                      splitter if splitter is not None else DataBalancer(seed=seed),
                      models, evaluator)


class MultiClassificationModelSelector:
    """Defaults (reference MultiClassificationModelSelector.scala): CV 3 folds,
    F1 metric, DataCutter."""

    @staticmethod
    def with_cross_validation(num_folds: int = 3, seed: int = 42,
                              splitter: Optional[Splitter] = None,
                              models=None, evaluator=None,
                              stratify: bool = False,
                              **validator_kw) -> ModelSelector:
        return _build("multiclass",
                      OpCrossValidation(num_folds=num_folds, seed=seed, stratify=stratify,
                                        **validator_kw),
                      splitter if splitter is not None else DataCutter(seed=seed),
                      models, evaluator)

    @staticmethod
    def with_train_validation_split(train_ratio: float = 0.75, seed: int = 42,
                                    splitter: Optional[Splitter] = None,
                                    models=None, evaluator=None,
                                    stratify: bool = False,
                                    **validator_kw) -> ModelSelector:
        return _build("multiclass",
                      OpTrainValidationSplit(train_ratio=train_ratio, seed=seed,
                                             stratify=stratify, **validator_kw),
                      splitter if splitter is not None else DataCutter(seed=seed),
                      models, evaluator)


class RegressionModelSelector:
    """Defaults (reference RegressionModelSelector.scala): CV 3 folds, RMSE,
    DataSplitter."""

    @staticmethod
    def with_cross_validation(num_folds: int = 3, seed: int = 42,
                              splitter: Optional[Splitter] = None,
                              models=None, evaluator=None,
                              **validator_kw) -> ModelSelector:
        return _build("regression",
                      OpCrossValidation(num_folds=num_folds, seed=seed,
                                        **validator_kw),
                      splitter if splitter is not None else DataSplitter(seed=seed),
                      models, evaluator)

    @staticmethod
    def with_train_validation_split(train_ratio: float = 0.75, seed: int = 42,
                                    splitter: Optional[Splitter] = None,
                                    models=None, evaluator=None,
                                    **validator_kw) -> ModelSelector:
        return _build("regression",
                      OpTrainValidationSplit(train_ratio=train_ratio, seed=seed,
                                             **validator_kw),
                      splitter if splitter is not None else DataSplitter(seed=seed),
                      models, evaluator)
