from repro.data.dirichlet import dirichlet_partition, label_distribution, heterogeneity_score
from repro.data.synthetic import (
    make_synthetic_classification,
    make_synthetic_images,
    make_federated_lm_corpus,
    make_synthetic_lm,
)
from repro.data.pipeline import FederatedData, FederatedTokens, lm_batch_iterator
from repro.data.population import (
    FaultyStore,
    HostPopulationStore,
    StreamingClientData,
    TransientStoreError,
    availability_log_weights,
    make_population_store,
)

__all__ = [
    "dirichlet_partition",
    "label_distribution",
    "heterogeneity_score",
    "make_synthetic_classification",
    "make_synthetic_images",
    "make_federated_lm_corpus",
    "make_synthetic_lm",
    "FederatedData",
    "FederatedTokens",
    "lm_batch_iterator",
    "FaultyStore",
    "HostPopulationStore",
    "StreamingClientData",
    "TransientStoreError",
    "availability_log_weights",
    "make_population_store",
]
