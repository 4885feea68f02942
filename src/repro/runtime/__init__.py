from .train_loop import TrainConfig, train
from .serve_loop import (DecodeReplica, MultiHostServingCluster, Request,
                         ServingCluster)
from .elastic import ElasticTrainer, ElasticReport

__all__ = ["TrainConfig", "train", "DecodeReplica",
           "MultiHostServingCluster", "Request", "ServingCluster",
           "ElasticTrainer", "ElasticReport"]
