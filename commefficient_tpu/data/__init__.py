from commefficient_tpu.data.fed_dataset import FedDataset
from commefficient_tpu.data.cifar import FedCIFAR10, FedCIFAR100
from commefficient_tpu.data.emnist import FedEMNIST
from commefficient_tpu.data.imagenet import FedImageNet
from commefficient_tpu.data.synthetic import SyntheticCV
from commefficient_tpu.data.offline import FedDigits, FedPatches32
from commefficient_tpu.data.tokens import FedTokens
from commefficient_tpu.data.sampler import FedSampler
from commefficient_tpu.data.batching import FedBatcher, val_batches

fed_datasets = {
    "CIFAR10": FedCIFAR10,
    "CIFAR100": FedCIFAR100,
    "EMNIST": FedEMNIST,
    "ImageNet": FedImageNet,
    "Synthetic": SyntheticCV,
    "Digits": FedDigits,
    "Patches32": FedPatches32,
}

__all__ = ["FedDataset", "FedTokens", "FedCIFAR10", "FedCIFAR100", "FedEMNIST",
           "FedImageNet", "SyntheticCV", "FedDigits", "FedPatches32",
           "FedSampler", "FedBatcher", "val_batches", "fed_datasets"]
