from .common import ZooModel, register_zoo_model
from .textclassification import TextClassifier
from .textgeneration import TransformerLM
from .commandaplus import CommandAPlusLM
from .granitehybrid import GraniteHybridLM
from .ouro import OuroLM
from .recommendation import (Recommender, NeuralCF, WideAndDeep,
                             UserItemFeature, UserItemPrediction,
                             ColumnFeatureInfo)
from .recommendation_utils import (hash_bucket, categorical_from_vocab_list,
                                   get_boundaries, get_negative_samples,
                                   get_wide_tensor, get_deep_tensor,
                                   row_to_feature, row_to_sample,
                                   to_user_item_feature,
                                   features_to_arrays)
from .image.classification import ImageClassifier, resnet50, label_output
from .image.detection import (ObjectDetector, ssd_vgg16, ssd_mobilenet,
                              decode_output, ScaleDetection, visualize,
                              Visualizer)
from .image.config import (ImageConfigure, PaddingParam, read_label_map,
                           read_imagenet_label_map, read_pascal_label_map,
                           read_coco_label_map)
