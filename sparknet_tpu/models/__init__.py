from .dsl import (
    net_param,
    java_data_layer,
    memory_data_layer,
    convolution_layer,
    pooling_layer,
    inner_product_layer,
    relu_layer,
    lrn_layer,
    dropout_layer,
    concat_layer,
    softmax_layer,
    softmax_with_loss_layer,
    accuracy_layer,
    layer,
    msg,
)
from .lenet import lenet
from .cifar10 import cifar10_quick, cifar10_full
from .alexnet import alexnet, caffenet
from .googlenet import googlenet
from .vgg import vgg16
from .laguna import laguna
from .lfm2 import lfm2
from .deepseek_v2 import deepseek_v2
